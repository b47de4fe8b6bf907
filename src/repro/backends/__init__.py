"""Quantized-execution backend registry.

One entry point — `dispatch(x, w, policy, act_scale)` — executes every
quantized matmul in the repo, and its KV-cache twin
`decode_attention(q, cache, pos, policy=...)` every serving decode-step
attention. `policy.backend` names a registered `QuantizedMatmulBackend`;
consumers (qlinear, model layers, the serving engine, benchmarks) never
branch on backend strings themselves.

Registered backends:
  xla              — dequantize-to-compute-dtype, XLA fuses decode into the
                     GEMM prologue; handles any rank and stacked weights
                     (also the fallback for unsupported operand layouts)
  pallas           — single fused pallas_call: in-kernel activation OVP
                     quantization + VMEM weight decode + scale epilogue
  pallas_interpret — same kernel, CPU interpreter (tests / this container)
  pallas_sharded   — the fused kernels under shard_map on the configured
                     mesh (`configure_mesh`): column/row tensor-parallel
                     2-D matmuls, expert-parallel grouped stacks,
                     Hkv-sharded decode/prefill attention (backends/sharded.py)
  pallas_sharded_interpret — the sharded backend over the interpret
                     kernels (the multi-host-CPU parity twin)
  reference        — pure-jnp fp32 oracle (equivalence tests)

Adding a backend: subclass `QuantizedMatmulBackend`, implement `matmul`
(and `supports` if partial), then `register(MyBackend())` — the name
becomes a valid `QuantPolicy.backend` value everywhere at once. See
docs/backends.md.

Observability: `dispatch_stats()` (served / declined-with-reason counts
per backend) and `act_scale_stats()` (static vs dynamic A-side scale
resolutions). The key vocabulary for both — and the full
`decline_reason` code registry — is machine-readable in
`backends/base.py` (`DECLINE_CODES` / `DISPATCH_KEYS` /
`DISPATCH_MARKERS` / `ACT_SCALE_KEYS`), re-exported here.
"""
from __future__ import annotations

import collections
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ovp import MixedExpertQuant, QuantizedTensor
from repro.core.policy import QuantPolicy

from .base import (ACT_SCALE_KEYS, ALL_DECLINE_CODES, DECLINE_CODES,
                   DISPATCH_KEYS, DISPATCH_MARKERS,
                   QuantizedMatmulBackend, act_normal_dtype,
                   act_scale_stats, decline, dispatch_key,
                   quantize_activation, reset_act_scale_stats,
                   resolve_act_scale)
from .pallas import PallasBackend, PallasInterpretBackend
from .reference import ReferenceBackend
from .sharded import (ShardedPallasBackend, ShardedPallasInterpretBackend,
                      configure_mesh, current_mesh)
from .xla import XlaBackend

_REGISTRY: Dict[str, QuantizedMatmulBackend] = {}


def register(backend: QuantizedMatmulBackend) -> QuantizedMatmulBackend:
    """Register (or override) a backend under `backend.name`."""
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> QuantizedMatmulBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown quantized-matmul backend {name!r}; "
                       f"registered: {available()}") from None


def available() -> list:
    return sorted(_REGISTRY)


for _b in (XlaBackend(), PallasBackend(), PallasInterpretBackend(),
           ReferenceBackend(), ShardedPallasBackend(),
           ShardedPallasInterpretBackend()):
    register(_b)
del _b

# REPRO_FORCE_INTERPRET=1 re-registers "pallas" (and its sharded sibling)
# as the interpret twin, so CI (no TPU) exercises the real kernel code
# paths — including grouped MoE dispatch and shard_map wrapping — under
# any config that names the compiled backend.
if os.environ.get("REPRO_FORCE_INTERPRET", "0") not in ("", "0"):
    class _ForcedInterpret(PallasInterpretBackend):
        name = "pallas"

    class _ForcedShardedInterpret(ShardedPallasInterpretBackend):
        name = "pallas_sharded"
    register(_ForcedInterpret())
    register(_ForcedShardedInterpret())


# --------------------------------------------------------------------------
# Dispatch statistics: fused-vs-fallback counts with machine-readable
# decline reasons. Counts accumulate at trace time (one per traced matmul
# call site), which is exactly the granularity kernels_bench reports.
# --------------------------------------------------------------------------
_DISPATCH_STATS: collections.Counter = collections.Counter()


def reset_dispatch_stats() -> None:
    _DISPATCH_STATS.clear()


def dispatch_stats() -> Dict[str, int]:
    """Counter keyed "backend" (served) / "backend->fallback:reason"
    (declined), with a `stacked` marker for 3-D weight stacks."""
    return dict(_DISPATCH_STATS)


def _record(backend_name: str, reason: Optional[str],
            marker: str = "") -> None:
    # dispatch_key validates both the reason code and the marker against
    # the base.py registry, so a typo'd decline string fails at the
    # dispatch site instead of surfacing as a mystery stats key
    _DISPATCH_STATS[dispatch_key(backend_name, reason, marker)] += 1


def pallas_call_eqns(fn, *args) -> list:
    """The pallas_call equations of fn's jaxpr, in program order
    (recursing through pjit/closed-call sub-jaxprs); each carries its
    `grid_mapping` in `params`."""
    closed = jax.make_jaxpr(fn)(*args)

    def sub_jaxprs(v):
        # params hold sub-jaxprs as ClosedJaxpr (.jaxpr), bare Jaxpr
        # (.eqns), or tuples/lists of either (e.g. lax.cond branches)
        if isinstance(v, (tuple, list)):
            for item in v:
                yield from sub_jaxprs(item)
        else:
            inner = getattr(v, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner
            elif hasattr(v, "eqns"):
                yield v

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for v in eqn.params.values():
                for inner in sub_jaxprs(v):
                    yield from walk(inner)

    return list(walk(closed.jaxpr))


def count_pallas_calls(fn, *args) -> int:
    """Number of pallas_call primitives in fn's jaxpr — the
    kernel-dispatch count of a pipeline. Benchmarks and tests use it to
    verify a backend's fusion claim (`dispatches_per_matmul`) against the
    traced program."""
    return len(pallas_call_eqns(fn, *args))


def dispatch(x: jax.Array, w, policy: QuantPolicy,
             act_scale: Optional[jax.Array] = None,
             precision=None, site: str = "") -> jax.Array:
    """Execute x (..., K) @ dequant(w) (K, N) on the policy's backend.

    Stacked per-expert weights (3-D `w.data`) take the grouped kernel on
    backends that support them; a `MixedExpertQuant` (per-expert mixed
    precision) dispatches each homogeneous group and stitches the outputs
    back into expert order (backends that cannot split ragged groups —
    `mixed_expert_decline_reason` — route the whole stack to their
    fallback). Falls back (one hop) when the requested backend declines
    the operand layout, recording the machine-readable reason in
    `dispatch_stats()` instead of asserting mid-trace. `site` is the
    "/"-joined weight address; layout-aware backends (the sharded one)
    classify column- vs row-parallel off its leaf name.
    """
    if isinstance(w, MixedExpertQuant):
        backend = get_backend(policy.backend)
        reason = backend.mixed_expert_decline_reason(x, w, policy)
        if reason is not None:
            _record(backend.name, reason, "[stacked]")
            policy = policy.with_backend(backend.fallback)
        return _dispatch_mixed_experts(x, w, policy, act_scale, precision,
                                       site)
    backend = get_backend(policy.backend)
    reason = backend.decline_reason(x, w, policy, site=site)
    _record(backend.name, reason, "[stacked]" if w.data.ndim > 2 else "")
    if reason is not None:
        backend = get_backend(backend.fallback)
    return backend.matmul(x, w, policy, act_scale=act_scale,
                          precision=precision, site=site)


def decode_attention(q: jax.Array, cache, pos: jax.Array, *,
                     policy: Optional[QuantPolicy] = None,
                     window: int = 0, ring: int = 0) -> jax.Array:
    """Execute single-token decode attention over a KV cache on the
    policy's backend (q: (B, 1, H, D); pos: (B,)).

    The KV-cache twin of `dispatch`: `policy.backend` (resolved per cache
    site by `models/layers.py::decode_attention`) picks the registered
    backend; the pallas backends run the fused decode-attention kernel —
    packed OVP caches unpack/dequantize PER KV TILE inside the kernel, no
    full-cache dequant ever traces — while `xla`/`reference` serve the
    dense dequant-then-einsum path. Layouts a kernel backend declines
    fall back (one hop) with the machine-readable reason recorded under a
    `"...[decode_attn]"` key in `dispatch_stats()`. `policy=None` is the
    dense XLA path (training / direct layer calls).
    """
    backend = get_backend(policy.backend if policy is not None else "xla")
    reason = backend.decode_attn_decline_reason(q, cache)
    _record(backend.name, reason, "[decode_attn]")
    if reason is not None:
        backend = get_backend(backend.fallback)
    return backend.decode_attention(q, cache, pos, window=window,
                                    ring=ring)


def prefill_attention(q: jax.Array, cache, positions: jax.Array, *,
                      policy: Optional[QuantPolicy] = None):
    """Execute paged cache-write prefill on the policy's backend
    (q: (1, C, H, D) chunk queries; cache: paged dict with block_table +
    raw stage_k/stage_v; positions: (1, C) absolute chunk positions).

    The prefill twin of `decode_attention`: the pallas backends run ONE
    pallas_call that both attends the chunk causally over the raw stage
    and OVP-quantizes every stage tile onto its physical page (no
    prefill-then-splice round trip); `xla`/`reference` serve the dense
    twin — bit-identical page bytes, attention equal up to softmax
    reassociation. Declines record a `"...[prefill_attn]"` key in
    `dispatch_stats()` and fall back one hop. Returns (out, new_cache).
    """
    backend = get_backend(policy.backend if policy is not None else "xla")
    reason = backend.prefill_attn_decline_reason(q, cache)
    _record(backend.name, reason, "[prefill_attn]")
    if reason is not None:
        backend = get_backend(backend.fallback)
    return backend.prefill_attention(q, cache, positions)


def _dispatch_mixed_experts(x: jax.Array, w: MixedExpertQuant,
                            policy: QuantPolicy,
                            act_scale: Optional[jax.Array],
                            precision, site: str = "") -> jax.Array:
    """Per-expert mixed precision: run each homogeneous group through the
    registry (so W4 groups and W8 groups each hit the grouped kernel) and
    scatter the group outputs back into the stacked expert order.

    Contract: only the WEIGHT side is per-expert — each group's precision
    comes from its QuantizedTensor (packed at quantization time under the
    expert's resolved rule). The A side, backend, and compute dtype come
    from the call-site `policy`, exactly as for any other dispatch; rule
    fields beyond weight precision (abits, backend, ...) do not vary
    within one stacked matmul. fp groups (rules that disable an expert)
    run a plain matmul with unquantized activations.

    `x` is the grouped lhs (…, E, C, K); expert membership is static
    (decided at quantization time), so the gathers/permutation lower to
    static slices under jit.
    """
    cdt = jnp.dtype(policy.compute_dtype)
    outs = []
    for qt, ids in zip(w.groups, w.expert_ids):
        idx = np.asarray(ids, dtype=np.int32)
        xg = jnp.take(x, idx, axis=-3)
        # per-slot scales carry the expert dim — gather it to match this
        # group's expert subset ((…, E, C) and (…, E, C, 1) layouts both
        # accepted; scalars / per-tensor scales pass through)
        scale = act_scale
        if scale is not None and getattr(scale, "ndim", 0):
            scale = jnp.asarray(scale)
            if scale.ndim >= 3 and scale.shape[-3] == w.n_experts \
                    and scale.shape[-1] == 1:
                scale = jnp.take(scale, idx, axis=-3)
            elif scale.ndim >= 2 and scale.shape[-2:] == x.shape[-3:-1]:
                scale = jnp.take(scale, idx, axis=-2)
        if isinstance(qt, QuantizedTensor):
            outs.append(dispatch(xg, qt, policy, act_scale=scale,
                                 precision=precision, site=site))
        else:  # fp group — the site policy resolved to "no quantization"
            outs.append(jnp.matmul(xg.astype(cdt), qt.astype(cdt),
                                   precision=precision))
    cat = jnp.concatenate([o.astype(cdt) for o in outs], axis=-3)
    flat_ids = np.concatenate([np.asarray(ids, dtype=np.int32)
                               for ids in w.expert_ids])
    order = np.argsort(flat_ids)
    return jnp.take(cat, order, axis=-3)


__all__ = ["QuantizedMatmulBackend", "register", "get_backend", "available",
           "DECLINE_CODES", "ALL_DECLINE_CODES", "DISPATCH_KEYS",
           "DISPATCH_MARKERS", "ACT_SCALE_KEYS", "decline", "dispatch_key",
           "dispatch", "decode_attention", "prefill_attention",
           "dispatch_stats",
           "reset_dispatch_stats",
           "act_scale_stats", "reset_act_scale_stats",
           "count_pallas_calls", "pallas_call_eqns", "quantize_activation",
           "resolve_act_scale", "act_normal_dtype", "XlaBackend",
           "PallasBackend", "PallasInterpretBackend", "ReferenceBackend",
           "ShardedPallasBackend", "ShardedPallasInterpretBackend",
           "configure_mesh", "current_mesh"]
