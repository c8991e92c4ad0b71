"""The roofline: the least time the chip could take for given operations and
bytes. The operations and bytes that a model requires are counted by its
family (``families/<family>/work.py``), from its shapes alone."""


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which of the two bounds it."""
    t_f = flops / peaks["flops_bf16"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
