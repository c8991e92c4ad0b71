"""Serving-side quantization: int8/fp8 weight-only GEMMs and the
quantized paged KV pool, calibrated through ``paddle_tpu.quantization``.

Two independent dtype axes, both default-OFF
(``FLAGS_serving_weight_dtype`` / ``FLAGS_serving_kv_dtype`` = "bf16" =
today's full-precision bitwise-exact path, untouched):

* **weights** — per-OUTPUT-CHANNEL symmetric scales computed at engine
  build (absmax of each output column) or imported from a PTQ
  calibration (``calibrate()``). The stored leaves become int8/fp8 with a
  float32 ``<name>_s`` scale companion; the dequant multiply rides the
  GEMM epilogue (``ops.pallas_kernels.quant_gemm`` on TPU, the same
  ``(x @ q.astype(dt)) * s`` algebra as a jnp fallback elsewhere), so no
  fp weight copy is ever materialized — including the mp rungs, where
  the int8 shard feeds ``fused_gemm_ag``'s epilogue directly and scales
  shard with their channels.
* **KV** — per-PAGE scales stored host-side beside the page table
  (``PagedKVPool.k_scale``/``v_scale``, uploaded as traced operands like
  the table itself): pages are the natural quantization block — CoW
  copies, prefix sharing and the trash-page masking all move quantized
  bytes and their scale entries together. Writes quantize in
  ``paged_kv_scatter``; dequant happens inside the paged-decode Pallas
  kernel's online-softmax loop and in the pure-jnp gather fallback. The
  scale VALUES come from per-layer |K|/|V| clip ranges: a PTQ
  calibration over a token sample (``calibrate``/``kv_ranges``), or an
  automatic one-forward calibration at engine build.

Exactness contract: "bitwise-exact" moves to "exact at a given dtype
config" — a quantized engine is still admission-order invariant,
kill-and-resume bitwise, and mp∈{2,4} output is bitwise identical to the
single-chip QUANTIZED output (per-channel quantization commutes with
column sharding; the gather-only schedule moves bytes, never math). The
bf16/bf16 config stays bitwise identical to the unquantized engine
because none of this code runs.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np
import jax
import jax.numpy as jnp

DTYPES = ("bf16", "int8", "fp8")
# serving storage dtype + symmetric max per quantized dtype ("bf16" means
# "leave at full precision" — the serving fp path never actually casts)
STORE_DTYPES = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}
QMAX = {"int8": 127.0, "fp8": 448.0}
# block-stacked matmul leaves that quantize (scale shape [L, out]);
# head_w quantizes too (scale [V]). Embeddings/norms/biases stay fp —
# the GEMM weights are where the HBM lives.
BLOCK_WEIGHTS = ("qkv_w", "out_w", "up_w", "down_w")


class QuantSpecError(ValueError):
    """A QuantSpec that cannot serve these params/config — unknown dtype,
    or calibrated scale/clip shapes that don't match the tree (the error
    names the offending leaf)."""


class QuantDtypeMismatchError(ValueError):
    """Snapshot dtype config != restoring engine's dtype config. Restoring
    quantized KV bytes into a pool of another dtype would deserialize
    garbage; the refusal names BOTH configs so the operator can rebuild
    the engine (or pick the right snapshot) instead of debugging NaNs."""

    def __init__(self, snap, mine):
        self.snapshot_config = tuple(snap)
        self.engine_config = tuple(mine)
        super().__init__(
            f"snapshot was taken at dtype config weight={snap[0]}/"
            f"kv={snap[1]} but this engine serves weight={mine[0]}/"
            f"kv={mine[1]}; build the restoring Engine with the snapshot's "
            f"quant config (quantized KV bytes do not reinterpret)")


@dataclass
class QuantSpec:
    """Static serving-quantization config + optional calibrated artifacts.

    ``weight_scales`` (optional) pins per-output-channel fp32 scales from
    a PTQ calibration: ``{"blocks": {leaf: [L, out]}, "head_w": [V]}`` on
    the LOGICAL qkv layout (the mp engine permutes qkv columns head-major
    together with the weights). ``kv_k_clip``/``kv_v_clip`` are per-layer
    symmetric |K|/|V| clip ranges ([L] float); the engine divides by its
    kv dtype's qmax to get the per-page scales. Leave them None to let
    the engine auto-calibrate (weights: absmax at build; KV: one fp
    forward over a deterministic token sample)."""

    weight_dtype: str = "bf16"
    kv_dtype: str = "bf16"
    weight_scales: dict | None = None
    kv_k_clip: np.ndarray | None = None
    kv_v_clip: np.ndarray | None = None

    def __post_init__(self):
        for name, d in (("weight_dtype", self.weight_dtype),
                        ("kv_dtype", self.kv_dtype)):
            if d not in DTYPES:
                raise QuantSpecError(
                    f"{name} must be one of {DTYPES}, got {d!r}")

    @property
    def active(self):
        return self.weight_dtype != "bf16" or self.kv_dtype != "bf16"

    @property
    def quantizes_weights(self):
        return self.weight_dtype != "bf16"

    @property
    def quantizes_kv(self):
        return self.kv_dtype != "bf16"

    def key(self):
        """Hashable static key for the memoized executable builders."""
        return (self.weight_dtype, self.kv_dtype)


def resolve(quant, flags):
    """Normalize the Engine's ``quant=`` argument: a QuantSpec passes
    through, a dtype string ("int8"/"fp8") quantizes both axes, and None
    reads ``FLAGS_serving_weight_dtype``/``FLAGS_serving_kv_dtype``.
    Returns None when the resolved config is the full-precision bf16/bf16
    path — the engine then runs byte-identical to the unquantized code."""
    if isinstance(quant, QuantSpec):
        return quant if quant.active else None
    if isinstance(quant, str):
        spec = QuantSpec(weight_dtype=quant, kv_dtype=quant)
        return spec if spec.active else None
    if quant is not None:
        raise QuantSpecError(
            f"quant= must be a QuantSpec, a dtype string or None, got "
            f"{type(quant).__name__}")
    wd = str(flags.get("FLAGS_serving_weight_dtype", "bf16"))
    kd = str(flags.get("FLAGS_serving_kv_dtype", "bf16"))
    spec = QuantSpec(weight_dtype=wd, kv_dtype=kd)
    return spec if spec.active else None


def page_scales(clip, num_pages, qmax):
    """THE per-page scale seeding rule, shared by ``PagedKVPool`` and the
    drift harness: every page of layer l starts at ``clip[l]/qmax``
    (floored at 1e-8), the trash page (physical 0) stays 1.0 — its
    garbage is never read unmasked, and a 1.0 divisor keeps trash writes
    finite. Returns [L, P] float32."""
    clip = np.asarray(clip, np.float64)
    out = np.ones((clip.shape[0], int(num_pages)), np.float32)
    out[:, 1:] = (np.maximum(clip, 1e-8) / float(qmax))[:, None]
    return out


# ---------------------------------------------------------------------------
# validation (up-front, naming the leaf)


def _expected_scale_shapes(params):
    out = {}
    blocks = params["blocks"]
    for name in BLOCK_WEIGHTS:
        w = np.shape(blocks[name])
        out[f"blocks.{name}"] = (w[0], w[-1])
    out["head_w"] = (np.shape(params["head_w"])[-1],)
    return out


def validate(spec, params, config):
    """Reject a spec whose calibrated artifacts don't match this params
    tree BEFORE anything is built — the error names the offending leaf."""
    if spec.weight_scales is not None:
        expected = _expected_scale_shapes(params)
        given = dict(spec.weight_scales)
        blocks = given.pop("blocks", {})
        flat = {f"blocks.{k}": v for k, v in blocks.items()}
        flat.update(given)
        for leaf, arr in flat.items():
            if leaf not in expected:
                raise QuantSpecError(
                    f"QuantSpec.weight_scales names leaf {leaf!r}, which "
                    f"is not a quantized serving weight "
                    f"({sorted(expected)})")
            shape = tuple(np.shape(arr))
            if shape != expected[leaf]:
                raise QuantSpecError(
                    f"QuantSpec.weight_scales[{leaf!r}] has shape {shape} "
                    f"but the params tree needs {expected[leaf]} "
                    f"(per-output-channel scales)")
        missing = [k for k in expected if k not in flat]
        if missing:
            raise QuantSpecError(
                f"QuantSpec.weight_scales is missing scales for "
                f"{missing}; calibrate() produces the full set")
    if spec.quantizes_kv:
        L = int(config.num_layers)
        for name, clip in (("kv_k_clip", spec.kv_k_clip),
                           ("kv_v_clip", spec.kv_v_clip)):
            if clip is not None and np.shape(clip) != (L,):
                raise QuantSpecError(
                    f"QuantSpec.{name} has shape {np.shape(clip)} but the "
                    f"model has {L} layers (one clip per layer)")
    return spec


# ---------------------------------------------------------------------------
# weight quantization


def _quantize_leaf(w, dtype, scale=None):
    """Per-output-channel symmetric quantization of a matmul weight
    [..., K, F] along its LAST axis. Channel-independent by construction,
    so a column shard of the result equals the result of the shard — the
    mp bitwise contract."""
    wf = jnp.asarray(w, jnp.float32)
    qmax = QMAX[dtype]
    if scale is None:
        amax = jnp.max(jnp.abs(wf), axis=-2)            # [..., F]
        scale = jnp.maximum(amax, 1e-8) / qmax
    else:
        scale = jnp.asarray(scale, jnp.float32)
    sb = scale[..., None, :]                            # broadcast over K
    if dtype == "int8":
        q = jnp.clip(jnp.round(wf / sb), -128, 127).astype(jnp.int8)
    else:
        q = jnp.clip(wf / sb, -qmax, qmax).astype(STORE_DTYPES[dtype])
    return q, scale.astype(jnp.float32)


def quantize_params(params, config, spec, qkv_perm=None):
    """Quantize the serving GEMM weights of an ``init_gpt_params`` tree to
    ``spec.weight_dtype``, adding a fp32 ``<name>_s`` scale leaf per
    quantized weight. Pinned ``spec.weight_scales`` are honored
    (``qkv_perm`` relabels the pinned qkv columns when the caller already
    permuted the tree head-major); otherwise scales are fresh absmax of
    the live weights — which is exactly what ``swap_params`` wants. A
    tree whose qkv stack is stored transposed (the engine's,
    ``generation._stored_qkv``) is quantized in the logical form."""
    from ..models.generation import _trained_qkv
    if not spec.quantizes_weights:
        return params
    params = _trained_qkv(params)
    pinned = spec.weight_scales or {}
    pinned_blocks = dict(pinned.get("blocks", {}))
    if qkv_perm is not None and "qkv_w" in pinned_blocks:
        pinned_blocks["qkv_w"] = np.asarray(
            pinned_blocks["qkv_w"])[..., qkv_perm]
    blocks = dict(params["blocks"])
    for name in BLOCK_WEIGHTS:
        q, s = _quantize_leaf(blocks[name], spec.weight_dtype,
                              pinned_blocks.get(name))
        blocks[name] = q
        blocks[name + "_s"] = s
    out = dict(params)
    out["blocks"] = blocks
    q, s = _quantize_leaf(params["head_w"], spec.weight_dtype,
                          pinned.get("head_w"))
    out["head_w"] = q
    out["head_w_s"] = s
    return out


def scale_bytes(params):
    """Total bytes of the fp32 scale leaves riding a quantized tree."""
    total = 0
    leaves = dict(params.get("blocks", {}))
    leaves["head_w_s"] = params.get("head_w_s")
    for name, a in leaves.items():
        if name.endswith("_s") and a is not None:
            total += int(np.prod(np.shape(a))) * 4
    return total


# ---------------------------------------------------------------------------
# calibration bridge (paddle_tpu.quantization observers -> QuantSpec)


def _observer_clip(obs):
    """Symmetric clip range recorded by an 8-bit observer: scales() is
    clip/qmax, so clip = scales() * (2^(bits-1) - 1)."""
    return np.asarray(obs.scales(), np.float64) * \
        (2.0 ** (obs.bit_length() - 1) - 1.0)


def _calibration_sample(config, n_tokens):
    """Deterministic token sample for the automatic (no-data) KV
    calibration: a fixed sweep over the vocabulary."""
    T = max(2, min(int(n_tokens), config.max_seq_len))
    return (np.arange(T, dtype=np.int32) * 7 + 1) % config.vocab_size


def kv_ranges(params, config, sample_ids=None, n_tokens=64,
              observer_factory=None):
    """Per-layer |K| / |V| clip ranges from ONE full-precision prefill
    over ``sample_ids`` (default: the deterministic sweep), recorded
    through ``quantization`` observers (AbsmaxObserver by default; pass
    e.g. ``lambda: PercentileObserver(99.9)`` to clip outliers). Returns
    (k_clip [L], v_clip [L]) float64 numpy arrays."""
    from ..models.generation import _forward_cached, _logical_qkv
    from ..quantization import AbsmaxObserver
    params = _logical_qkv(params, config)
    if sample_ids is None:
        sample_ids = _calibration_sample(config, n_tokens)
    ids = jnp.asarray(np.asarray(sample_ids, np.int32))[None]
    T = ids.shape[1]
    if T > config.max_seq_len:
        raise QuantSpecError(
            f"calibration sample ({T} tokens) exceeds the model's "
            f"max_seq_len ({config.max_seq_len})")
    L = config.num_layers
    nh = config.num_heads
    d = config.hidden_size // nh
    compute = jnp.dtype(config.compute_dtype or "float32")
    kc = jnp.zeros((L, 1, T, nh, d), compute)
    vc = jnp.zeros((L, 1, T, nh, d), compute)
    _, kc, vc = _forward_cached(params, config, ids, kc, vc, 0)
    make = observer_factory or AbsmaxObserver
    k_clip = np.zeros(L)
    v_clip = np.zeros(L)
    for layer in range(L):
        ok, ov = make(), make()
        ok.observe(kc[layer])
        ov.observe(vc[layer])
        ok.cal_thresholds()
        ov.cal_thresholds()
        k_clip[layer] = float(np.max(_observer_clip(ok)))
        v_clip[layer] = float(np.max(_observer_clip(ov)))
    return k_clip, v_clip


def calibrate(params, config, sample_ids=None, weight_dtype="int8",
              kv_dtype="int8", kv_observer=None):
    """PTQ calibration bridge: run the ``quantization`` package's
    observers against the params tree and a token sample, producing a
    serving ``QuantSpec`` (per-output-channel weight scales + per-layer
    KV clip ranges) that ``Engine(quant=...)``, ``Predictor.serve()`` and
    ``inference.serve()`` accept. Scales are recorded on the LOGICAL qkv
    layout (the mp engine permutes them with the weights)."""
    from ..models.generation import _logical_qkv
    from ..quantization import PerChannelAbsmaxObserver
    spec = QuantSpec(weight_dtype=weight_dtype, kv_dtype=kv_dtype)
    if spec.quantizes_weights:
        logical = _logical_qkv(params, config)
        qmax = QMAX[weight_dtype]
        blocks = {}
        for name in BLOCK_WEIGHTS:
            w = np.asarray(logical["blocks"][name], np.float32)
            # one per-channel observer per layer: quant_axis is the OUT
            # (last) axis of this layer's [K, F] slice
            scales = []
            for layer in range(w.shape[0]):
                obs = PerChannelAbsmaxObserver(quant_axis=w.ndim - 2)
                obs.observe(w[layer])
                obs.cal_thresholds()
                scales.append(np.maximum(
                    _observer_clip(obs), 1e-8) / qmax)
            blocks[name] = np.stack(scales).astype(np.float32)
        obs = PerChannelAbsmaxObserver(quant_axis=1)
        obs.observe(np.asarray(logical["head_w"], np.float32))
        obs.cal_thresholds()
        head_s = (np.maximum(_observer_clip(obs), 1e-8) / qmax
                  ).astype(np.float32)
        spec = replace(spec, weight_scales={"blocks": blocks,
                                            "head_w": head_s})
    if spec.quantizes_kv:
        k_clip, v_clip = kv_ranges(params, config, sample_ids,
                                   observer_factory=kv_observer)
        spec = replace(spec, kv_k_clip=k_clip, kv_v_clip=v_clip)
    return validate(spec, params, config)


def ensure_kv_clips(spec, params, config):
    """Fill missing KV clip ranges by auto-calibration (one fp forward
    over the deterministic sample) — the flags-only path where no PTQ
    artifact exists. Returns the (possibly updated) spec."""
    if not spec.quantizes_kv or (spec.kv_k_clip is not None
                                 and spec.kv_v_clip is not None):
        return spec
    k_clip, v_clip = kv_ranges(params, config)
    return replace(spec,
                   kv_k_clip=spec.kv_k_clip if spec.kv_k_clip is not None
                   else k_clip,
                   kv_v_clip=spec.kv_v_clip if spec.kv_v_clip is not None
                   else v_clip)


# ---------------------------------------------------------------------------
# drift measurement (the smoke harness' gate metric)


def max_logit_drift(params, config, spec, prompt, page_size=8):
    """Max |logits_fp - logits_quant| of ONE prefill forward over
    ``prompt`` through the paged serving forward — the drift stat the
    memory-equal smoke rung gates and ``serving_summary()`` surfaces.
    Returns (max_abs_drift, max_abs_fp_logit)."""
    from ..models.generation import _logical_qkv
    from .paged_kv import pages_for
    from .paged_attention import paged_forward
    params = _logical_qkv(params, config)
    spec = ensure_kv_clips(spec, params, config)
    prompt = np.asarray(prompt, np.int32)
    T = len(prompt)
    L = config.num_layers
    nh = config.num_heads
    d = config.hidden_size // nh
    MP = pages_for(T, page_size)
    P = MP + 1
    compute = jnp.dtype(config.compute_dtype or "float32")
    ids = jnp.asarray(prompt)[None]
    start = jnp.zeros((1,), jnp.int32)
    valid = jnp.asarray([T], jnp.int32)
    table = jnp.asarray(np.arange(1, MP + 1, dtype=np.int32))[None]

    def run(p, kv_dtype, kv_scales):
        store = (compute if kv_dtype == "bf16"
                 else STORE_DTYPES[kv_dtype])
        kc = jnp.zeros((L, P, page_size, nh, d), store)
        vc = jnp.zeros((L, P, page_size, nh, d), store)
        logits, _, _ = paged_forward(p, config, ids, kc, vc, start, valid,
                                     table, page_size, False,
                                     kv_scales=kv_scales)
        return np.asarray(logits, np.float64)

    ref = run(params, "bf16", None)
    qparams = quantize_params(params, config, spec)
    kv_scales = None
    if spec.quantizes_kv:
        qmax = QMAX[spec.kv_dtype]
        kv_scales = (jnp.asarray(page_scales(spec.kv_k_clip, P, qmax)),
                     jnp.asarray(page_scales(spec.kv_v_clip, P, qmax)))
    got = run(qparams, spec.kv_dtype, kv_scales)
    return float(np.max(np.abs(ref - got))), float(np.max(np.abs(ref)))


# ---------------------------------------------------------------------------
# speculative-draft plumbing (the draft model is DERIVED, never loaded)


DRAFT_SOURCES = ("quant", "shallow")


@dataclass
class DraftSpec:
    """Static speculative-decoding config: how many tokens the draft
    proposes per boundary (``k``) and where the draft model comes from —
    ``"quant"`` (int8 self-draft: the engine's own weights quantized
    per-channel; degenerates to the engine weights when the engine is
    already quantized) or ``"shallow"`` (the first ``layers`` transformer
    blocks of the same tree, sharing embeddings/final-LN/head).
    ``layers=0`` means auto (num_layers // 2, at least 1)."""

    k: int
    source: str = "quant"
    layers: int = 0

    def __post_init__(self):
        self.k = int(self.k)
        if self.k < 1:
            raise QuantSpecError(
                f"DraftSpec.k must be >= 1, got {self.k}")
        if self.source not in DRAFT_SOURCES:
            raise QuantSpecError(
                f"DraftSpec.source must be one of {DRAFT_SOURCES}, got "
                f"{self.source!r}")
        self.layers = int(self.layers)
        if self.layers < 0:
            raise QuantSpecError(
                f"DraftSpec.layers must be >= 0 (0 = auto), got "
                f"{self.layers}")

    def num_layers(self, total_layers):
        if self.source != "shallow":
            return int(total_layers)
        n = self.layers or max(1, int(total_layers) // 2)
        return min(n, int(total_layers))

    def key(self):
        """Hashable static key for the memoized draft builder."""
        return (self.k, self.source, self.layers)


def resolve_draft(speculate_k, source, layers, flags):
    """Normalize the Engine's speculation arguments: explicit kwargs win,
    None falls back to the FLAGS_serving_speculate_k family. Returns None
    when the resolved k is 0 — the engine then builds byte-identical
    executables to a pre-speculation engine."""
    k = (int(flags.get("FLAGS_serving_speculate_k", 0))
         if speculate_k is None else int(speculate_k))
    if k <= 0:
        return None
    src = (str(flags.get("FLAGS_serving_draft_source", "quant"))
           if source is None else str(source))
    n = (int(flags.get("FLAGS_serving_draft_layers", 0))
         if layers is None else int(layers))
    return DraftSpec(k=k, source=src, layers=n)


def shallow_draft_params(params, n_layers):
    """Truncate a (possibly quantized) params tree to its first
    ``n_layers`` transformer blocks. Embeddings, final LN and the LM head
    are SHARED with the full tree (same arrays, no copy); only the
    stacked block leaves — and their ``_s`` scale companions, which stack
    the same layer axis — are sliced."""
    blocks = {name: leaf[:n_layers]
              for name, leaf in params["blocks"].items()}
    out = dict(params)
    out["blocks"] = blocks
    return out
