"""Mean time from a request's admission into a slot to its first token (its
chunk steps and the boundaries between them): ``prefill_span_s`` over
``first_tokens`` of the program's serving counters over the window. With
``queue_wait_ms`` it splits the engine's side of the time to first token."""


def read(ctx):
    c = ctx.counters
    if not ctx.on_chip or not c.get("first_tokens"):
        return None
    return c["prefill_span_s"] / c["first_tokens"]
