"""Share of the page-table entries of the window's [slots, 1] decode
dispatches that the attention read visits: ``decode_pages_swept`` over
``decode_pages_table`` of the program's serving counters. The decode kernel
visits the pages a slot holds, the gather read the whole table (100);
nothing to read from a program that does not count them."""


def read(ctx):
    c = ctx.counters
    if not c.get("decode_pages_table") or "decode_pages_swept" not in c:
        return None
    return 100.0 * c["decode_pages_swept"] / c["decode_pages_table"]
