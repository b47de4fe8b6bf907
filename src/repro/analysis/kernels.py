"""Kernel-contract pass: abstract-eval every registered pallas_call.

Each `Case` traces one kernel entry point with `jax.make_jaxpr` (no
execution, no accelerator) and walks the jaxpr for `pallas_call`
equations; their `grid_mapping` / `input_output_aliases` params carry
the whole tiling contract. Checks, per pallas_call:

- **KC_NO_PALLAS_CALL** — the entry point traced to zero pallas_calls
  (the fused path silently fell back; the case is vacuous).
- **KC_BLOCK_INDIVISIBLE** — a block shape does not divide its operand
  shape. The wrappers in `kernels/ops.py` own padding and clamp blocks,
  so a non-divisor tile means a guard and a BlockSpec disagree.
- **KC_PAIR_SPLIT** — a K tile splits an outlier-victim pair: for int8
  codes (1 value per row) the K block must be even; packed nibbles are
  whole pairs by construction. Also sweeps
  `backends.sharded.row_shard_pair_aligned` against an independent
  shard-boundary ground truth.
- **KC_PAGE_TILE** — a paged kv/scale pool is tiled with a block that is
  not one whole page: the block-table indirection gathers per *page*,
  so any other tile reads across page boundaries.
- **KC_ALIAS_MISSING** — a kernel that rewrites pool leaves does not
  declare `input_output_aliases` for them (pages no tile touches would
  come back uninitialized instead of intact).
- **KC_VMEM_BUDGET** — the summed live-block footprint (block shape x
  itemsize over every operand and output) exceeds the budget
  (default 16 MiB ~ one TPU core's VMEM; override with
  `--vmem-budget` or `REPRO_VMEM_BUDGET`).

Fixture modules may define `analysis_cases() -> [dict]` (Case kwargs);
their cases are appended to the repo set.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import math
import os
from pathlib import Path
from typing import Callable, List, Optional, Sequence, Tuple

from . import Finding

DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024


@dataclasses.dataclass
class Case:
    """One traced kernel entry point plus its contract expectations.

    `build()` returns `(fn, args)`; the pass traces `fn(*args)`.
    `pair_blocks` lists `(array_shape, axis, values_per_row)` operands
    whose K tile must hold whole pairs; `page_tiles` lists
    `(array_shape, axis)` pool operands whose tile must be one whole
    page; `min_aliases` is the number of input->output alias pairs the
    call must declare. Operands are matched by exact array shape.
    """
    name: str
    build: Callable[[], Tuple[Callable, tuple]]
    pair_blocks: Tuple[Tuple[Tuple[int, ...], int, int], ...] = ()
    page_tiles: Tuple[Tuple[Tuple[int, ...], int], ...] = ()
    min_aliases: int = 0


# --------------------------------------------------------------------------
# jaxpr walking (same recursion as backends.count_pallas_calls)
# --------------------------------------------------------------------------
def _sub_jaxprs(v):
    if isinstance(v, (tuple, list)):
        for item in v:
            yield from _sub_jaxprs(item)
    else:
        inner = getattr(v, "jaxpr", None)
        if inner is not None:
            yield inner
        elif hasattr(v, "eqns"):
            yield v


def _iter_pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            for inner in _sub_jaxprs(v):
                yield from _iter_pallas_eqns(inner)


def _blocks(eqn):
    """[(block_shape_ints, array_shape, itemsize)] for every operand and
    output of one pallas_call equation."""
    gm = eqn.params["grid_mapping"]
    out = []
    for bm in gm.block_mappings:
        sds = bm.array_aval
        dims = (getattr(d, "block_size", d) for d in bm.block_shape)
        block = tuple(d for d in dims if isinstance(d, int))
        out.append((block, tuple(sds.shape), sds.dtype.itemsize))
    return out


def _kernel_name(eqn) -> str:
    info = eqn.params.get("name_and_src_info")
    return getattr(info, "name", None) or str(info)


def _alias_count(eqn) -> int:
    aliases = eqn.params.get("input_output_aliases") or ()
    if isinstance(aliases, dict):
        return len(aliases)
    return len(tuple(aliases))


# --------------------------------------------------------------------------
# The repo's kernel entry points as cases
# --------------------------------------------------------------------------
def _repo_cases() -> List[Case]:
    import jax.numpy as jnp
    import repro.backends  # noqa: F401 — entering the package through
    # kernels/ first would trip the core<->backends import cycle
    from repro.kernels import (decode_attn, ovp_encode, ovp_matmul,
                               prefill_attn)

    def mk_fused_w4():
        a = jnp.zeros((1, 128, 256), jnp.float32)
        sa = jnp.ones((1, 128, 1), jnp.float32)
        wd = jnp.zeros((128, 128), jnp.uint8)     # K/2 packed rows
        sw = jnp.ones((1, 128), jnp.float32)
        fn = functools.partial(ovp_matmul.fused_ovp_matmul_kernel,
                               w_dtype="int4", a_mode="fp", interpret=True)
        return fn, (a, sa, wd, sw)

    def mk_fused_w8():
        a = jnp.zeros((1, 128, 256), jnp.float32)
        sa = jnp.ones((1, 128, 1), jnp.float32)
        wd = jnp.zeros((256, 128), jnp.uint8)     # K int8 rows
        sw = jnp.ones((1, 128), jnp.float32)
        fn = functools.partial(ovp_matmul.fused_ovp_matmul_kernel,
                               w_dtype="int8", a_mode="fp", interpret=True)
        return fn, (a, sa, wd, sw)

    def mk_grouped_w4():
        a = jnp.zeros((1, 2, 128, 256), jnp.float32)
        sa = jnp.ones((1, 2, 128, 1), jnp.float32)
        wd = jnp.zeros((2, 128, 128), jnp.uint8)
        sw = jnp.ones((2, 1, 128), jnp.float32)
        fn = functools.partial(ovp_matmul.grouped_ovp_matmul_kernel,
                               w_dtype="int4", a_mode="fp", interpret=True)
        return fn, (a, sa, wd, sw)

    def mk_grouped_w8():
        a = jnp.zeros((1, 2, 128, 256), jnp.float32)
        sa = jnp.ones((1, 2, 128, 1), jnp.float32)
        wd = jnp.zeros((2, 256, 128), jnp.uint8)
        sw = jnp.ones((2, 1, 128), jnp.float32)
        fn = functools.partial(ovp_matmul.grouped_ovp_matmul_kernel,
                               w_dtype="int8", a_mode="fp", interpret=True)
        return fn, (a, sa, wd, sw)

    def mk_encode():
        u = jnp.zeros((256, 512), jnp.float32)
        return functools.partial(ovp_encode.ovp_encode_pallas,
                                 interpret=True), (u,)

    hkv, g, d, ps, n_pages, n_log = 2, 2, 16, 8, 4, 2
    h = hkv * g

    def mk_decode_slab():
        s = 32
        cache = {"k_data": jnp.zeros((1, s, hkv, d // 2), jnp.uint8),
                 "v_data": jnp.zeros((1, s, hkv, d // 2), jnp.uint8),
                 "k_scl": jnp.ones((1, s, hkv), jnp.float32),
                 "v_scl": jnp.ones((1, s, hkv), jnp.float32)}
        q = jnp.zeros((1, 1, h, d), jnp.float32)
        pos = jnp.array([7], jnp.int32)
        fn = functools.partial(decode_attn.fused_decode_attention,
                               interpret=True)
        return (lambda q, pos: fn(q, cache, pos)), (q, pos)

    def _paged_pools():
        return {"k_data": jnp.zeros((n_pages, ps, hkv, d // 2), jnp.uint8),
                "v_data": jnp.zeros((n_pages, ps, hkv, d // 2), jnp.uint8),
                "k_scl": jnp.ones((n_pages, ps, hkv), jnp.float32),
                "v_scl": jnp.ones((n_pages, ps, hkv), jnp.float32),
                "block_table": jnp.arange(n_log, dtype=jnp.int32)[None]}

    def mk_decode_paged():
        cache = _paged_pools()
        q = jnp.zeros((1, 1, h, d), jnp.float32)
        pos = jnp.array([ps * n_log - 1], jnp.int32)
        fn = functools.partial(decode_attn.fused_decode_attention,
                               interpret=True)
        return (lambda q, pos: fn(q, cache, pos)), (q, pos)

    def mk_prefill_paged():
        c = 4
        cache = _paged_pools()
        cache["stage_k"] = jnp.zeros((1, ps * n_log, hkv, d), jnp.float32)
        cache["stage_v"] = jnp.zeros((1, ps * n_log, hkv, d), jnp.float32)
        q = jnp.zeros((1, c, h, d), jnp.float32)
        positions = jnp.arange(c, dtype=jnp.int32)[None]
        fn = functools.partial(prefill_attn.fused_prefill_attention,
                               interpret=True)
        return (lambda q, positions: fn(q, cache, positions)), (q, positions)

    pool_d = (n_pages, ps, hkv, d // 2)
    pool_s = (n_pages, ps, hkv)
    page_tiles = (((pool_d), 1), ((pool_s), 1))
    return [
        Case("fused_matmul_w4a16", mk_fused_w4),
        # int8 codes reach the kernel as (2, K/2, N) even/odd planes: one
        # row across the plane axis is one pair (2 values)
        Case("fused_matmul_w8a16", mk_fused_w8,
             pair_blocks=(((2, 128, 128), 1, 2),)),
        Case("grouped_matmul_w4a16", mk_grouped_w4),
        Case("grouped_matmul_w8a16", mk_grouped_w8,
             pair_blocks=(((2, 2, 128, 128), 2, 2),)),
        Case("ovp_encode", mk_encode),
        Case("decode_attn_slab_packed", mk_decode_slab),
        Case("decode_attn_paged_packed", mk_decode_paged,
             page_tiles=page_tiles),
        Case("prefill_attn_paged_packed", mk_prefill_paged,
             page_tiles=page_tiles, min_aliases=4),
    ]


def _load_fixture_cases(path: Path) -> List[Case]:
    spec = importlib.util.spec_from_file_location(
        f"_analysis_fixture_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    maker = getattr(mod, "analysis_cases", None)
    if maker is None:
        return []
    return [c if isinstance(c, Case) else Case(**c) for c in maker()]


# --------------------------------------------------------------------------
# Checks
# --------------------------------------------------------------------------
def _check_case(case: Case, vmem_budget: int) -> List[Finding]:
    import jax
    findings: List[Finding] = []
    fn, args = case.build()
    closed = jax.make_jaxpr(fn)(*args)
    eqns = list(_iter_pallas_eqns(closed.jaxpr))
    if not eqns:
        return [Finding("KC_NO_PALLAS_CALL", case.name,
                        "entry point traced to zero pallas_calls — the "
                        "fused path silently fell back")]

    all_blocks = []
    for eqn in eqns:
        kname = _kernel_name(eqn)
        where = f"{case.name}/{kname}"
        blocks = _blocks(eqn)
        all_blocks.extend(blocks)
        for block, arr, _ in blocks:
            for bdim, adim in zip(block, arr[-len(block):] if block
                                  else arr):
                if bdim and adim % bdim:
                    findings.append(Finding(
                        "KC_BLOCK_INDIVISIBLE", where,
                        f"block {block} does not divide operand {arr}"))
                    break
        footprint = sum(math.prod(block) * itemsize
                        for block, _, itemsize in blocks)
        if footprint > vmem_budget:
            findings.append(Finding(
                "KC_VMEM_BUDGET", where,
                f"live-block footprint {footprint} B exceeds the VMEM "
                f"budget {vmem_budget} B"))

    def _find(shape):
        return [b for b in all_blocks if b[1] == tuple(shape)]

    for arr_shape, axis, vpr in case.pair_blocks:
        hits = _find(arr_shape)
        if not hits:
            findings.append(Finding(
                "KC_PAIR_SPLIT", case.name,
                f"no pallas operand with shape {tuple(arr_shape)} — pair "
                f"tiling contract is unverifiable"))
            continue
        for block, arr, _ in hits:
            if (block[axis] * vpr) % 2:
                findings.append(Finding(
                    "KC_PAIR_SPLIT", case.name,
                    f"K tile {block} of operand {arr} holds "
                    f"{block[axis] * vpr} values along axis {axis} — an "
                    f"odd count splits an outlier-victim pair"))

    for arr_shape, axis in case.page_tiles:
        for block, arr, _ in _find(arr_shape):
            if block[axis] != arr[axis]:
                findings.append(Finding(
                    "KC_PAGE_TILE", case.name,
                    f"pool {arr} tiled with block {block}: the kv tile "
                    f"along axis {axis} is {block[axis]}, not the page "
                    f"size {arr[axis]}"))

    if case.min_aliases:
        declared = max(_alias_count(eqn) for eqn in eqns)
        if declared < case.min_aliases:
            findings.append(Finding(
                "KC_ALIAS_MISSING", case.name,
                f"kernel rewrites {case.min_aliases} pool leaves but "
                f"declares only {declared} input_output_aliases"))
    return findings


def _shard_boundary_aligned(k_rows: int, tp: int, packed: bool) -> bool:
    """Independent ground truth for the row-parallel K split: pairs are
    consecutive value indices (2p, 2p+1), shards hold contiguous row
    ranges, and every shard must locally decode whole pairs — so K must
    divide and every shard's END (including the last one's, the total
    value count) must land on an even value index."""
    if k_rows % tp != 0:
        return False
    per_shard = (k_rows // tp) * (2 if packed else 1)
    return all((s * per_shard) % 2 == 0 for s in range(1, tp + 1))


def _check_shard_split() -> List[Finding]:
    from repro.backends.sharded import row_shard_pair_aligned
    findings: List[Finding] = []
    for packed in (False, True):
        for tp in (1, 2, 3, 4, 8):
            for k_rows in range(1, 65):
                got = row_shard_pair_aligned(k_rows, tp, packed)
                want = _shard_boundary_aligned(k_rows, tp, packed)
                if got != want:
                    findings.append(Finding(
                        "KC_SHARD_SPLIT",
                        "backends/sharded.py::row_shard_pair_aligned",
                        f"k_rows={k_rows} tp={tp} packed={packed}: "
                        f"predicate says {got}, shard-boundary ground "
                        f"truth says {want}"))
    return findings


def check(fixtures: Sequence[str] = (),
          vmem_budget: Optional[int] = None) -> List[Finding]:
    if vmem_budget is None:
        vmem_budget = int(os.environ.get("REPRO_VMEM_BUDGET",
                                         DEFAULT_VMEM_BUDGET))
    cases = _repo_cases()
    for f in fixtures:
        if str(f).endswith(".py"):
            cases.extend(_load_fixture_cases(Path(f)))
    findings: List[Finding] = []
    for case in cases:
        findings.extend(_check_case(case, vmem_budget))
    findings.extend(_check_shard_split())
    return findings
