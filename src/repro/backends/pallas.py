"""Fused Pallas backend: one `pallas_call` per quantized matmul.

Activation OVP quantization runs as the kernel prologue at a precomputed
per-tensor/per-row scale (no packed activation tensor in HBM, no XLA
encode -> kernel decode round trip), weight codes decode in VMEM, and both
scales apply in the accumulator epilogue. 2-D and 3-D lhs share the kernel:
leading lhs dims fold into M, so a serving decode step's `(slots, 1, K)`
rows form one M tile and each weight tile is fetched and decoded once per
call, not once per slot.

Stacked per-expert weights `(E, K, N)` run the grouped kernel: an expert
grid dim indexes the weight stack, the lhs carries a matching `(…, E, C,
K)` layout (the MoE dispatch tensor), and per-expert scales apply in the
epilogue. Layouts the kernels genuinely cannot execute are declined with a
machine-readable reason (`decline_reason`) and dispatch falls back to XLA.

Static calibrated activation scales (`policy.act_scale_mode == "static"`
with a per-site `static_act_scale` attached by
`calibration.apply_calibration`) skip the per-step scale computation
entirely: no 3σ std runs, and the kernel takes the calibrated scale as a
single (1, 1) scalar operand in place of the whole per-row scale plane —
one compiled kernel serves every calibrated site (see the `*_static`
kernel bodies in `kernels/ovp_matmul.py`).

Serving decode steps additionally route their ATTENTION through this
backend: `decode_attention` runs the fused decode-attention kernel
(`kernels/decode_attn.py`) that unpacks/dequantizes OVP-packed KV caches
per tile in VMEM — no full-cache dequant, no dense rematerialization —
with length/ring/window masking in-kernel from the traced position
(fp caches take the same kernel minus the unpack phase). Unsupported
(q, cache) layouts decline with a `decode_*` reason code and fall back
to the dense XLA path (see docs/kv_cache.md).

Decline-reason codes and the `dispatch_stats()` / `act_scale_stats()` key
vocabulary are registered once, in `backends/base.py::DECLINE_CODES` (and
`DISPATCH_KEYS` / `ACT_SCALE_KEYS`); every reason this backend returns
goes through `decline()` so unregistered codes fail at the return site.

`pallas_interpret` is the same backend with `interpret=True` — the CPU
emulation used by tests and this container; numerics are identical.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.ovp import QuantizedTensor
from repro.core.policy import QuantPolicy
from repro.kernels import decode_attn, ops, prefill_attn

from .base import (QuantizedMatmulBackend, act_normal_dtype, decline,
                   record_act_scale, resolve_act_scale)


def _static_const_scale(policy: QuantPolicy, act_scale) -> Optional[float]:
    """The activation scale as a Python float when it is a calibrated
    per-site scalar: static mode with the policy's scale (or an explicit
    Python scalar). Array scales — per-row statics, dynamic 3σ — return
    None and take the per-row scale-operand path instead."""
    if policy.act_scale_mode != "static":
        return None
    if act_scale is None:
        return None if policy.static_act_scale is None \
            else float(policy.static_act_scale)
    return float(act_scale) if isinstance(act_scale, (int, float)) else None


class PallasBackend(QuantizedMatmulBackend):
    name = "pallas"
    interpret = False
    fuses_act_encode = True
    dispatches_per_matmul = 1

    def decline_reason(self, x, w: QuantizedTensor, policy: QuantPolicy,
                       site: str = "") -> Optional[str]:
        if w.pair_axis % 2 != 0:
            # pairing must run along K (quantize_weight guarantees -2)
            return decline("pair_axis_not_reduction")
        if w.data.ndim == 2:
            return None if x.ndim >= 2 else decline("lhs_rank_lt_2")
        if w.data.ndim == 3:
            # grouped path: lhs must carry the matching expert dim at -3
            if x.ndim < 3:
                return decline("grouped_lhs_rank_lt_3")
            if x.shape[-3] != w.data.shape[0]:
                return decline("grouped_lhs_expert_mismatch")
            return None
        return decline("stacked_rank_gt_3")

    def matmul(self, x: jax.Array, w: QuantizedTensor, policy: QuantPolicy,
               act_scale: Optional[jax.Array] = None,
               precision=None, site: str = "") -> jax.Array:
        cdt = jnp.dtype(policy.compute_dtype)
        a_dtype = None
        scale = None
        static = None
        if policy.abits:
            static = _static_const_scale(policy, act_scale)
            if static is not None:
                # calibrated scalar: no std, and the kernel reads one
                # (1, 1) scale word instead of a per-row plane
                a_dtype = act_normal_dtype(policy)
                record_act_scale("static")
            else:
                scale, a_dtype = resolve_act_scale(x, policy, act_scale)
        if w.data.ndim == 3:
            return ops.grouped_ovp_matmul(x, w, a_dtype=a_dtype,
                                          act_scale=scale,
                                          static_act_scale=static,
                                          out_dtype=cdt,
                                          interpret=self.interpret)
        return ops.fused_ovp_matmul(x, w, a_dtype=a_dtype, act_scale=scale,
                                    static_act_scale=static, out_dtype=cdt,
                                    interpret=self.interpret)

    # -- fused decode attention (kernels/decode_attn.py) ------------------
    fuses_decode_attention = True

    def decode_attn_decline_reason(self, q, cache) -> Optional[str]:
        # the kernel module names the reason; decline() re-validates it
        # against the base.py registry at the backend boundary
        return decline(decode_attn.decline_reason(q, cache))

    def decode_attention(self, q: jax.Array, cache, pos: jax.Array, *,
                         window: int = 0, ring: int = 0) -> jax.Array:
        return decode_attn.fused_decode_attention(
            q, cache, pos, window=window, ring=ring,
            interpret=self.interpret)

    # -- fused cache-write prefill (kernels/prefill_attn.py) ---------------
    fuses_prefill_attention = True

    def prefill_attn_decline_reason(self, q, cache) -> Optional[str]:
        return decline(prefill_attn.prefill_decline_reason(q, cache))

    def prefill_attention(self, q: jax.Array, cache, positions: jax.Array):
        return prefill_attn.fused_prefill_attention(
            q, cache, positions, interpret=self.interpret)


class PallasInterpretBackend(PallasBackend):
    name = "pallas_interpret"
    interpret = True
