"""The paged step compiled for a described (not attached) v5e chip, at the
serving cells' attention geometry and two layers: what XLA:TPU makes of the
KV pool. The CPU jaxpr gate (test_paged_serving) shows the pool is the layer
scan's carry; this shows the chip's compiler then updates it in place — no
copy, zero fill or relayout of a whole pool, the outputs in the donated
buffers — and that the decode kernel keeps the name the benchmark finds it
by. That the qkv product reads the engine's stack as stored, with no copy
of a layer's slice. And what it makes of the sampling tail: a conditional
whose branches hold the sorts, so a greedy dispatch skips them. And the
jamba cell's steps at published widths: pools and states in place at their
logical bytes, the selective-scan kernels over the whole state.

The topology is described inside a fixture (never at import: one process
holds the TPU library, and every xdist worker imports this file)."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import init_gpt_params
from paddle_tpu.serving import engine as E
from paddle_tpu.serving.operands import StepLayout
from paddle_tpu.serving.paged_attention import pool_head_dim
from paddle_tpu.serving.served_model import GPT

PAGE, SLOTS, MAX_SEQ = 16, 16, 2048


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the persistent compile cache cannot read back what was built for a
    # described chip (it warns and compiles again): keep these out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _gpt(hidden, heads, vocab=1024):
    return GPTConfig(vocab_size=vocab, hidden_size=hidden, num_layers=2,
                     num_heads=heads, max_seq_len=MAX_SEQ, dropout=0.0,
                     use_flash=False, compute_dtype="bfloat16", remat=False)


def _compile_step(chip, cfg, num_pages, batch, window, use_kernel):
    """The engine's own builder, lowered on shapes placed on the described
    chip; the tree as the engine prepares it and the pool as the engine
    allocates it."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, "bfloat16"),
        jax.eval_shape(lambda: GPT.prepare(
            init_gpt_params(cfg, jax.random.key(0)), cfg)))
    pool = sds((cfg.num_layers, num_pages, PAGE, cfg.num_heads,
                pool_head_dim(cfg.hidden_size // cfg.num_heads)), "bfloat16")
    step = E._make_paged_step(E._cfg_key(cfg), None, PAGE, use_kernel,
                              (1, 2))
    layout = StepLayout(batch, window, (MAX_SEQ // PAGE,))
    return pool, step.lower(params, pool, pool, sds((layout.size,), "int32"),
                            layout=layout).compile()


GPT_STEPS = pytest.mark.parametrize(
    "name,hidden,heads,num_pages,batch,window", [
        ("1.3B-decode", 2048, 16, 2049, SLOTS, 1),
        ("1.3B-chunk", 2048, 16, 2049, 1, 16),
        # head_dim 80 in a pool of 128 lanes: the kernel takes it too
        ("2.7B-decode", 2560, 32, 769, SLOTS, 1),
        ("2.7B-chunk", 2560, 32, 769, 1, 16),
    ])


@GPT_STEPS
def test_paged_step_updates_the_pool_in_place_on_the_chip(
        chip, name, hidden, heads, num_pages, batch, window):
    """The step as the engine builds it on a TPU, with the kernel: a Mosaic
    call in the [16, 1] step, none in the chunk step."""
    pool, compiled = _compile_step(chip, _gpt(hidden, heads), num_pages,
                                   batch, window, True)
    text = compiled.as_text()
    dims = ",".join(str(n) for n in pool.shape)
    whole = re.findall(
        rf"(%[\w.\-]+) = bf16\[{dims}\]\S* ([\w\-]+)\(", text)
    # (a dynamic-update-slice INTO the carried pool is the in-place write
    # of a row; one that had to copy first would show the copy)
    moved = [(op, code) for op, code in whole
             if code in ("copy", "copy-start", "broadcast", "transpose")]
    assert not moved, f"{name}: the whole pool is moved by {moved}"
    # both pools come back in the buffers they came in
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * 2 * pool.size
    calls = re.findall(r"(%[\w.\-]+) = \S+ custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    if window == 1:
        assert [c for c in calls if c.startswith("%paged_decode_attention")]
    else:
        assert not calls


@GPT_STEPS
def test_gpt_step_reads_the_qkv_stack_as_stored_on_the_chip(
        chip, name, hidden, heads, num_pages, batch, window):
    """The GPT cells' steps (ROADMAP S16): no copy or transpose of the qkv
    stack, of a layer's slice of it in either order, or of that slice
    split by heads. The layer scan's read of the slice stays: the product
    needs it."""
    _, compiled = _compile_step(chip, _gpt(hidden, heads), num_pages, batch,
                                window, True)
    L, H, d = 2, hidden, hidden // heads
    qkv = {f"{n},{H},{3 * H}" for n in (1, L)} | \
        {f"{n},{3 * H},{H}" for n in (1, L)} | {f"3,{heads},{d},{H}"}
    moved = re.findall(r"= bf16\[([\d,]+)\]\S* (copy|copy-start|transpose)\(",
                       compiled.as_text())
    assert not [m for m in moved if m[0] in qkv], moved


def test_decode_kernel_takes_the_gather_reads_windows_off_the_2_7b_step(
        chip):
    """The [16, 1] step at 2.7B's attention geometry (32 heads of 80 in 128
    lanes): built with the kernel its temporaries are below the gather
    read's, whose float32 windows [16, 2048, 32, 80] of K and V are gone."""
    temp = {}
    for kernel in (True, False):
        _, compiled = _compile_step(chip, _gpt(2560, 32), 769, SLOTS, 1,
                                    kernel)
        temp[kernel] = compiled.memory_analysis().temp_size_in_bytes
    window = SLOTS * MAX_SEQ * 32 * 80 * 4
    assert temp[True] < temp[False] - window, temp


def test_decode_step_keeps_the_sampling_tail_in_a_conditional_on_the_chip(
        chip):
    """The [16, 1] step of the 1.3B cell at its own vocabulary: XLA:TPU
    keeps generation._next_token's cond a real ``conditional`` (it does not
    flatten it into a select), so a greedy dispatch runs neither sort of the
    nucleus cut: both lie in branch computations, none in the entry."""
    _, compiled = _compile_step(chip, _gpt(2048, 16, vocab=50304), 2049,
                                SLOTS, 1, True)
    text = compiled.as_text()
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M).group(1)
    assert re.search(r" conditional\(", entry)
    assert len(re.findall(r" sort\(", text)) == 2
    assert not re.search(r" sort\(", entry)


def _compile_expert_step(chip, name, slots, chunk, max_seq):
    """A benchmark configuration's [1, chunk] step for the described chip:
    every expert width as published, one period, the vocabulary cut to 1024
    (the head is not what this looks at); the engine's own builder and its
    pools as the engine allocates them."""
    import json
    import os
    from paddle_tpu.serving.paged_kv import ring_pages
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", f"{name}.json")
    with open(path) as f:
        d = dict(json.load(f), vocab_size=1024)
    if d["family"] == "afmoe":
        from paddle_tpu.models import afmoe as M
        cfg = M.AfmoeConfig.from_dict(d, compute_dtype="bfloat16")
        init = M.init_afmoe_params
    else:
        from paddle_tpu.models import lfm2 as M
        cfg = M.Lfm2Config.from_dict(d, compute_dtype="bfloat16")
        init = M.init_lfm2_params

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, "bfloat16"),
        jax.eval_shape(lambda: init(cfg, jax.random.key(0))))
    model = cfg.served_model
    pools, widths = [], []
    for g in model.geometry(cfg).groups:
        if not g.paged:
            shape, width = g.state_shape(slots), 0
        else:
            tokens = max_seq if g.window is None else min(
                max_seq, PAGE * ring_pages(g.window, chunk, PAGE))
            width = -(-tokens // PAGE)
            shape = g.pool_shape(slots * width + 1, PAGE)
        widths.append(width)
        pools += [sds(shape, "bfloat16")] * len(g.names)
    step = E._make_paged_step(model.key(cfg), None, PAGE, False,
                              tuple(range(1, 1 + len(pools))), model=model)
    layout = StepLayout(1, chunk, tuple(widths))
    compiled = step.lower(params, *pools, sds((layout.size,), "int32"),
                          layout=layout).compile()
    return params["moe"], compiled


@pytest.mark.parametrize("name,slots,chunk,max_seq", [
    ("trinity-mini", 16, 512, 6400),
    ("lfm2-24B-A2B", 64, 256, 1536),
])
def test_expert_chunk_step_reads_the_stacks_as_stored_on_the_chip(
        chip, name, slots, chunk, max_seq):
    """The expert cells' widest chunk step (ROADMAP S15): no copy or
    transpose of an expert stack or of a layer's slice of one (the dense
    form's hoisted layout copies were 4.3 and 3.2 GB of temporaries), and
    the grouped products take each stack whole, with the layer's index."""
    moe, compiled = _compile_expert_step(chip, name, slots, chunk, max_seq)
    text = compiled.as_text()
    stacks = {",".join(map(str, moe[k].shape))
              for k in ("experts_gate_w", "experts_up_w", "experts_down_w")}
    layer = {s.split(",", 1)[1] for s in stacks}
    moved = re.findall(r"= bf16\[([\d,]+)\]\S* (copy|copy-start|transpose|"
                       r"dynamic-slice)\(", text)
    assert not [m for m in moved if m[0] in stacks | layer], moved
    # a kernel's line names its operands' shapes in its layout constraints
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for dims in stacks:
        assert [c for c in calls if f"bf16[{dims}]" in c], dims
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def _compile_jamba_step(chip, batch, window, slots=128, max_seq=8192):
    """The jamba cell's step for the described chip at the published widths
    and depth (the vocabulary cut to 1024: the head is not what this looks
    at), the pools and states as the engine allocates them: K and V of
    twice what the slots hold, the convolution rows in bfloat16, the
    recurrent state in float32."""
    import json
    import os
    from paddle_tpu.models import jamba as M
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "jamba2-3B.json")
    with open(path) as f:
        cfg = M.JambaConfig.from_dict(dict(json.load(f), vocab_size=1024),
                                      compute_dtype="bfloat16")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, "bfloat16"),
        jax.eval_shape(lambda: M.init_jamba_params(cfg, jax.random.key(0))))
    model = cfg.served_model
    geo = model.geometry(cfg)
    pools, widths = [], []
    for g in geo.groups:
        width = max_seq // PAGE if g.paged else 0
        shape = g.pool_shape(2 * slots * width + 1, PAGE) if g.paged \
            else g.state_shape(slots)
        widths.append(width)
        pools += [sds(shape, g.dtype or geo.dtype)] * len(g.names)
    # the engine's choice on a TPU (``kernel_ok``: one KV head)
    step = E._make_paged_step(model.key(cfg), None, PAGE, True,
                              tuple(range(1, 1 + len(pools))), model=model)
    layout = StepLayout(batch, window, tuple(widths))
    compiled = step.lower(params, *pools, sds((layout.size,), "int32"),
                          layout=layout).compile()
    return pools, compiled


@pytest.mark.parametrize("batch,window,kernel", [(1, 512, "ssm_scan"),
                                                 (128, 1, "ssm_step")],
                         ids=["chunk_1x512", "decode_128x1"])
def test_jamba_step_updates_pages_and_states_in_place_on_the_chip(
        chip, batch, window, kernel):
    """The jamba cell's widest chunk step and its decode step: the pools
    and both states come back in the buffers they came in at their logical
    bytes (a row of three bfloat16 rows of 5,120 took 4/3 of its bytes and
    two relayouts a dispatch before it became one row of 15,360); no copy
    of the recurrent state or of a layer's slice of it; the selective-scan
    kernel takes the state whole, under its name, and the decode step's
    attention read is a kernel over the pool as stored (the gather it
    replaced moved the table's whole width, 1.07 GB a step)."""
    pools, compiled = _compile_jamba_step(chip, batch, window)
    text = compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        p.size * p.dtype.itemsize for p in pools)
    kv, _, conv, ssm = (",".join(map(str, p.shape)) for p in pools)
    slice_ = ssm.split(",", 1)[1]
    moved = re.findall(r"= (?:bf16|f32)\[([\d,]+)\]\S* (copy|copy-start|"
                       r"transpose|broadcast|dynamic-slice)\(", text)
    assert not [m for m in moved if m[0] in (kv, conv, ssm, slice_)], moved
    calls = [line.lstrip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    scans = [c for c in calls if c.startswith(f"%{kernel}")]
    assert scans and all(f"f32[{ssm}]" in c for c in scans)
    # the decode step's attention read is the multi-query kernel's, over
    # the pool as it is stored; the chunk step gathers its window
    reads = [c for c in calls if c.startswith("%paged_mqa_decode")]
    assert len(scans) + len(reads) == len(calls)
    assert bool(reads) == (window == 1)
    assert all(f"bf16[{kv}]" in c for c in reads)
