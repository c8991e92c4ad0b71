"""Planted faults of the afmoe family, for the readings that the cell's limit
is set from (``tools/serve_readings.py``): each is a context in which the
family's plain reference computes a model that is wrong in one way, so that
put in the program's place it has to come out not correct. Not part of the
family's interface and never used by a run of the benchmark."""
import contextlib

import jax.numpy as jnp

from . import reference as ref


@contextlib.contextmanager
def _patched(module, name, new):
    old = getattr(module, name)
    setattr(module, name, new)
    try:
        yield
    finally:
        setattr(module, name, old)


def window_reads_whole_context():
    """A window layer that attends to its whole causal prefix: what a cache
    of one group of layers, read through one table, would serve."""
    return _patched(ref, "sees", lambda i, j, layer_type, cfg: j <= i)


def rotated_full_layer():
    """The full layers rotated like the window layers."""
    return _patched(ref, "ROTATED", ("sliding_attention", "full_attention"))


def dropped_gate():
    """The context goes to the output projection without its sigmoid gate."""
    return _patched(ref, "gate", lambda ctx, g: ctx)


def wrong_kv_head():
    """Query head j reads KV head ``j mod kv heads`` (the heads of a group
    strided), where the model's groups are contiguous."""
    return _patched(ref, "kv_head_of", lambda cfg: jnp.arange(
        cfg["num_attention_heads"]) % cfg["num_key_value_heads"])


def _short_ring(pages, page_size, chunk):
    """A window layer whose ring has ``pages(cfg)`` pages where a chunk of
    ``chunk`` positions needs ``(sliding_window + chunk) / page_size``
    (page-aligned): the chunk's last pages overwrite the oldest pages its
    first rows still read, so a row loses the keys of those pages. Every
    row is taken as a row of a whole chunk, as in prefill."""
    sees = ref.sees

    def short(i, j, layer_type, cfg):
        ok = sees(i, j, layer_type, cfg)
        if layer_type != "sliding_attention":
            return ok
        chunk_end = (i // chunk) * chunk + chunk - 1
        return ok & (j // page_size > chunk_end // page_size - pages(cfg))

    return _patched(ref, "sees", short)


def ring_without_the_chunks_room(page_size=16, chunk=512):
    """A ring of ``sliding_window / page_size + 1`` pages, what a decode
    step alone reads: a chunk's first row then loses up to ``chunk /
    page_size - 1`` of its window's pages."""
    return _short_ring(lambda cfg: cfg["sliding_window"] // page_size + 1,
                       page_size, chunk)


def ring_one_page_short(page_size=16, chunk=512):
    """A ring one page short of what a chunk needs: the first rows of a
    chunk lose the window's oldest page."""
    return _short_ring(
        lambda cfg: (cfg["sliding_window"] + chunk) // page_size - 1,
        page_size, chunk)


FAULTS = {"window_reads_whole_context": window_reads_whole_context,
          "rotated_full_layer": rotated_full_layer,
          "dropped_gate": dropped_gate,
          "wrong_kv_head": wrong_kv_head,
          "ring_without_the_chunks_room": ring_without_the_chunks_room}
# planted and read, but under what a comparison of served tokens resolves at
# the cell's sizes (one page of a window's 128, for the first 15 rows of a
# chunk: the same 125 tokens on the chip, PERF.md section 7); it fails at the
# rehearsal's size, where a page is half the window
UNRESOLVED = {"ring_one_page_short": ring_one_page_short}
