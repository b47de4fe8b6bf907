"""Batched serving engine: continuous batching over fixed decode slots.

Requests queue up; free slots take the next request (prefill), all active
slots step together (one batched decode). Slots free on EOS / max-tokens.
`step()` is the ONE step API — it returns `StepEvents` (every token
sampled this step, attributed to its request) and both serve loops build
on it: the synchronous `run_until_drained` batch loop here, and the
asyncio streaming front end in `serve/frontend.py` (per-request token
streams + TTFT/TPOT SLO metrics via `serve/metrics.py`; see
docs/serving.md).
Weights can be OliVe-PTQ-quantized (`quantize_params`), the KV cache
OVP-packed (policy.kv_bits=4), and activation quantization can run on
calibrated *static* scales (`EngineCfg.calibration`, validated up front —
zero per-step scale computations; see docs/calibration.md) — the paper's
serving story end to end.

Decode-step attention routes through the backend registry
(`backends.decode_attention`, resolved per cache site): on the pallas
backends the fused decode-attention kernel (`kernels/decode_attn.py`)
consumes OVP-packed caches IN PLACE — nibbles unpack per KV tile inside
the kernel, no full-cache dequant ever traces, and in-kernel masking from
the traced positions means one compiled decode step serves every
active-length mix in the slots. `EngineCfg.backend` overrides the
policy's backend for these sites too. See docs/kv_cache.md.

PAGED mode (`EngineCfg.page_pool`): instead of one dense
`(batch_slots, max_len)` slab per cache site, every site shares a global
pool of fixed-size OVP-packed pages (`serve/paging.py`); a per-slot block
table maps logical token rows to physical pages and admission reserves a
request's WORST-CASE pages up front (`PagePool.can_alloc`), so a request
never OOMs mid-decode — it queues instead. Prefill runs CHUNKED: `_admit`
stages the prompt and `step()` interleaves at most ONE fixed-size prefill
chunk per engine step into the running decode batch (a long prompt never
stalls decode for more than one chunk), each chunk one fused
cache-write-prefill dispatch (`backends.prefill_attention`) that attends
the raw staged prompt AND quantizes every stage tile onto its pages —
no `_splice_slot` round trip. Decode gathers K/V tiles through the block
table inside the same fused decode kernel (page size == kv tile size).
Slots free their pages on completion; `defrag()` compacts the pool.
"""
from __future__ import annotations

import collections
import contextlib
import copy
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import backends
from repro.analysis import sanitize
from repro.core.calibration import (CalibrationArtifact,
                                    MissingStaticScaleError,
                                    apply_calibration, static_scale_misses,
                                    uses_static_scales)
from repro.models.model import Model
from repro.serve.paging import PagePool, PagePoolCfg, pages_for


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # (T,) int32
    max_new_tokens: int = 16
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # why the request stopped: "eos" | "max_new_tokens" | "length_cap"
    # (hit cfg.max_len - 1 — previously a silent truncation)
    finish_reason: Optional[str] = None
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    # next-token logits of the prompt's last position, on the host
    # (kept only with EngineCfg.keep_prefill_logits)
    prefill_logits: Optional[np.ndarray] = None


@dataclasses.dataclass
class TokenEvent:
    """One sampled token, attributed to its request — the unit both the
    async streaming front end (`serve/frontend.py`) and the metrics
    ledger (`serve/metrics.py`) consume. Emitted the same engine step the
    token is sampled: prefill tokens carry `first=True` (the TTFT token),
    and the request's terminal token carries `done`/`finish_reason`."""
    uid: int
    token: int
    index: int                  # 0-based position in Request.out_tokens
    first: bool                 # True for the prefill (TTFT) token
    done: bool
    finish_reason: Optional[str] = None


@dataclasses.dataclass
class StepEvents:
    """What one `ServingEngine.step()` did, in consumable form.

    This is the step API both serve loops share: `run_until_drained`
    (batch/benchmark mode) and the asyncio front end both just call
    `step()` and read the returned events — neither reaches into slots
    or diffs `out_tokens`. All counts are PER STEP; lifetime counters
    live in `ServingEngine.stats()`.
    """
    step: int                   # 0-based engine step index
    t_start: float              # time.monotonic() at step entry / exit
    t_end: float
    admitted: List[int]         # uids leaving the queue this step
    prefill_chunks: int         # chunked-prefill dispatches run (0 or 1)
    decode_batch: int           # active slots in this step's batched decode
    tokens: List[TokenEvent]    # every token sampled this step
    queue_depth: int            # queued requests AFTER the step
    active: int                 # occupied decode slots after the step
    prefilling: int             # requests mid-chunked-prefill after the step
    # host seconds of each `engine.<phase>` span this step (a nested
    # phase, such as "tables", also counts in the phase that called it)
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    table_uploads: int = 0      # block-table uploads (`_sync_tables`)


@dataclasses.dataclass
class _Prefilling:
    """One request mid-chunked-prefill (paged mode): pages are already
    reserved, the raw prompt K/V accumulates in per-site stage buffers,
    and `step()` feeds one chunk per engine step until `written` covers
    the prompt."""
    req: Request
    slot: int
    toks: np.ndarray        # (stage_len,) right-padded prompt
    t: int                  # true prompt length
    chunk: int              # tokens per chunk (page-size multiple)
    stage_len: int          # staged rows (trace key; page-size multiple)
    stage_tiles: int        # stage_len // page_size
    pages: List[int]        # physical pages, logical order
    gen_pages: int          # pages kept after prefill (decode horizon)
    target: int             # chunked tokens to process: ceil(t/chunk)*chunk
    written: int            # tokens already prefilled
    stage: object           # per-site {"stage_k","stage_v"} pytree


@dataclasses.dataclass
class EngineCfg:
    batch_slots: int = 4
    max_len: int = 256
    eos_id: int = -1            # -1: no EOS, run to max_new_tokens
    greedy: bool = True
    # quantized-matmul execution backend override; None keeps the model
    # policy's backend. Must name a `repro.backends` registry entry.
    backend: Optional[str] = None
    # calibrated static activation scales (see docs/calibration.md): baked
    # into the model policy at engine construction via `apply_calibration`.
    # With `act_scale_mode="static"` anywhere in the policy, construction
    # validates that EVERY static-mode quantized site has a scale —
    # misses raise the machine-readable `MissingStaticScaleError` up
    # front instead of mid-trace on the first prefill.
    calibration: Optional[CalibrationArtifact] = None
    # paged KV cache (serve/paging.py): replaces the per-site
    # (batch_slots, max_len) slab with a global page pool + block tables,
    # chunked prefill, and page-level admission control. Needs a pure
    # attn/moe block pattern. None = slab mode (unchanged).
    page_pool: Optional[PagePoolCfg] = None
    # chunked-prefill chunk size in tokens (paged mode; rounded up to a
    # page multiple). 0 = whole prompt in one chunk. Either way at most
    # ONE chunk runs per engine step, interleaved with decode.
    prefill_chunk: int = 0
    # LRU cap on the per-bucket jitted-prefill cache: with exact-length
    # prefill (non-bucketable block patterns) the cache previously grew
    # one entry per distinct prompt length, without bound.
    prefill_cache_cap: int = 8
    # device mesh for the sharded backends: a `runtime.elastic.MeshPlan`
    # (or a built `jax.sharding.Mesh`), installed via
    # `backends.configure_mesh` at engine construction so the
    # `pallas_sharded*` backends see it. None leaves any process-level
    # mesh untouched — without one those backends decline every call with
    # `shard_no_mesh` and serve through their dense fallback.
    mesh: Optional[object] = None
    # copy each request's prefill logits to the host (Request.
    # prefill_logits): lets a check compare backends on the served path.
    # Off by default — one vocab-wide row per request.
    keep_prefill_logits: bool = False


class ServingEngine:
    """Single-host reference engine (the multi-host path shards the same
    jitted steps over the mesh via pjit; see launch/serve.py)."""

    def __init__(self, model: Model, params, cfg: EngineCfg):
        # REPRO_SANITIZE=1: jax_debug_nans + checkified steps + the
        # trace audit (no-op otherwise; see repro.analysis.sanitize)
        sanitize.configure()
        if cfg.backend is not None and \
                model.policy.backends() != frozenset((cfg.backend,)):
            # shallow-copy so the override never leaks into other users of
            # the caller's Model instance (`with_backend` rewrites every
            # rule of a policy program)
            model = copy.copy(model)
            model.policy = model.policy.with_backend(cfg.backend)
        if cfg.calibration is not None:
            model = copy.copy(model)
            model.policy = apply_calibration(model.policy, cfg.calibration)
        # resolve every rule's backend through the registry up front: a
        # typo'd backend name fails here, not mid-trace on first prefill
        for name in model.policy.backends():
            backends.get_backend(name)
        if cfg.mesh is not None:
            # install the mesh BEFORE any trace so the sharded backends'
            # decline checks see the real model-axis size from step one
            backends.configure_mesh(cfg.mesh)
        # static-scale completeness: every quantized site that will
        # quantize activations at a calibrated scale must actually have
        # one. Fails at construction with the full site list (the
        # mid-trace backstop can only name one site at a time).
        if uses_static_scales(model.policy):
            misses = static_scale_misses(params, model.policy)
            if misses and cfg.calibration is not None \
                    and not getattr(model, "unrolled", False) \
                    and any(k.lower().startswith("layers/")
                            for k in cfg.calibration.as_dict()):
                # the artifact was calibrated on the unrolled layout but
                # this model (and its quantized tree) is still scanned —
                # its sites are blocks/<j>, so no layers/<i> key can ever
                # match. Diagnose the layout, not just the misses.
                raise ValueError(
                    "calibration artifact keys address the unrolled "
                    "layers/<i> layout but this model is scanned "
                    "(blocks/<j> sites). Apply the artifact with "
                    "apply_calibration() BEFORE build_model / "
                    "quantize_params so the program unrolls the model "
                    "(launch/serve.py does this; see docs/calibration.md)"
                    ", or key the artifact by blocks/<j>")
            if misses:
                raise MissingStaticScaleError(misses)
        self.model = model
        self.params = params
        self.cfg = cfg
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: List[Optional[Request]] = [None] * cfg.batch_slots
        self.pos = np.zeros((cfg.batch_slots,), np.int32)
        self.completed: List[Request] = []
        self._uid = 0
        # Bucketed prefill right-pads the prompt so the trace is keyed by
        # the bucket length, not the exact prompt length. Under a causal
        # index mask real tokens never attend the trailing pads and the pad
        # cache rows sit beyond `pos`, where decode overwrites them before
        # they can become valid — but recurrent states and ring (sliding-
        # window) caches DO absorb trailing garbage, so those block types
        # keep the exact-length path.
        self._bucket_ok = all(bt in ("attn", "moe")
                              for bt in model.cfg.block_pattern)
        self.prefill_traces = 0  # trace counter (tests assert bucket reuse)
        self.decode_traces = 0   # the single decode jit should trace once
        self._prefill_jits = 0   # jit entries built (traces > jits means
        #                          a jitted entry silently retraced)
        self.prefill_cache_evictions = 0
        self.prefill_chunks_run = 0
        self.steps_run = 0
        # per-step event buffers, drained into the StepEvents that
        # `step()` returns (see the StepEvents docstring)
        self._token_events: List[TokenEvent] = []
        self._admitted_uids: List[int] = []
        self._phases: Dict[str, float] = {}
        self._table_uploads = 0

        self.paged = cfg.page_pool is not None
        if self.paged:
            if not self._bucket_ok:
                raise ValueError(
                    f"page_pool needs a pure attn/moe block pattern "
                    f"(ring/recurrent state does not page); got "
                    f"{model.cfg.block_pattern}")
            pp = cfg.page_pool
            # table width covers the BUCKETED stage of the longest prompt,
            # not just max_len (buckets round up to powers of two)
            self.pages_per_row = pages_for(self._bucket(cfg.max_len),
                                           pp.page_size)
            n_pages = pp.n_pages or cfg.batch_slots * self.pages_per_row
            self.pool = PagePool(n_pages, pp.page_size)
            self._bt = np.zeros((cfg.batch_slots, self.pages_per_row),
                                np.int32)
            self.caches = model.init_paged_caches(
                n_pages, pp.page_size, cfg.batch_slots, self.pages_per_row,
                dtype=jnp.float32)
            self._prefilling: collections.deque = collections.deque()
            self._prefill_slots: set = set()
            # inactive slots decode in the batch like everyone else (the
            # batched step has no per-row gating); park their write index
            # past the table capacity so the scatter DROPS instead of
            # landing on page 0, which a live request may own
            self._pos_parked = self.pages_per_row * pp.page_size
            self.pos[:] = self._pos_parked
            self._sync_tables()
        else:
            self.caches = model.init_caches(cfg.batch_slots, cfg.max_len,
                                            dtype=jnp.float32)

        def prefill_one(params, caches, tokens, length):
            """Prefill one slot row; `tokens` (1, bucket) right-padded,
            `length` the true prompt length (traced, so one jit trace
            serves every prompt in the bucket)."""
            self.prefill_traces += 1
            logits, new_caches, _ = self.model.forward(
                params, {"tokens": tokens}, mode="prefill", caches=caches)
            return jnp.take(logits, length - 1, axis=1), new_caches

        def decode_step(params, caches, tokens, pos):
            self.decode_traces += 1
            logits, new_caches, _ = self.model.forward(
                params, {"tokens": tokens, "pos": pos}, mode="decode",
                caches=caches)
            return logits[:, 0], new_caches

        def prefill_chunk(params, caches, tokens, positions, len_m1):
            """One chunked-prefill dispatch (paged mode): tokens (1, C) of
            one request, positions (1, C) absolute, `len_m1` the prompt's
            last index (traced — the chunk offset and the logit read both
            trace, so ONE jit trace per stage length serves every chunk of
            every prompt in the bucket)."""
            self.prefill_traces += 1
            logits, new_caches, _ = self.model.forward(
                params, {"tokens": tokens}, mode="prefill", caches=caches,
                positions=positions)
            idx = jnp.clip(len_m1 - positions[0, 0], 0,
                           tokens.shape[1] - 1)
            return jnp.take(logits, idx, axis=1), new_caches

        self._decode = sanitize.jit_checked(decode_step)
        self._prefill = prefill_one  # jit per prompt-length bucket below
        self._prefill_chunk = prefill_chunk
        # LRU over jitted prefill entries (keyed by bucket / stage length)
        self._prefill_cache: "collections.OrderedDict[object, Callable]" \
            = collections.OrderedDict()

    # -------------------------------------------------------------- API
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> int:
        self._uid += 1
        self.queue.append(Request(uid=self._uid,
                                  prompt=np.asarray(prompt, np.int32),
                                  max_new_tokens=max_new_tokens,
                                  t_submit=time.monotonic()))
        return self._uid

    def _bucket(self, n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return b

    def _jit_prefill(self, key, fn) -> Callable:
        """Jitted-prefill cache with an LRU cap: exact-length prefill
        (non-bucketable patterns) keys on the raw prompt length, which is
        unbounded over a long-running serve."""
        cache = self._prefill_cache
        if key in cache:
            cache.move_to_end(key)
            return cache[key]
        jitted = sanitize.jit_checked(fn)
        self._prefill_jits += 1
        cache[key] = jitted
        while len(cache) > max(1, self.cfg.prefill_cache_cap):
            cache.popitem(last=False)
            self.prefill_cache_evictions += 1
        return jitted

    def trace_audit(self) -> Dict[str, int]:
        """Jit-trace ledger for the sanitizer's retrace audit: a prefill
        trace the bucket/stage-length cache should have absorbed, or a
        decode jit tracing more than once, counts as unexpected (a
        shape/dtype/weak-type drifted between calls meant to share one
        trace). `repro.analysis.sanitize.audit_traces` fails on it."""
        return {
            "prefill_traces": self.prefill_traces,
            "prefill_jits": self._prefill_jits,
            "decode_traces": self.decode_traces,
            "unexpected_retraces":
                max(0, self.prefill_traces - self._prefill_jits)
                + max(0, self.decode_traces - 1),
        }

    # ------------------------------------------------- paged-cache helpers
    @staticmethod
    def _map_sites(tree, fn):
        """Apply fn to every paged cache-site dict (detected by its
        "block_table" key) in a cache pytree."""
        if isinstance(tree, dict):
            if "block_table" in tree:
                return fn(tree)
            return {k: ServingEngine._map_sites(v, fn)
                    for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(ServingEngine._map_sites(v, fn)
                              for v in tree)
        return tree

    @staticmethod
    def _pair_sites(a, b, fn):
        """Zip two cache pytrees (a drives the structure) and apply fn at
        each paged site pair."""
        if isinstance(a, dict):
            if "block_table" in a:
                return fn(a, b)
            return {k: ServingEngine._pair_sites(a[k], b[k], fn)
                    for k in a}
        if isinstance(a, (list, tuple)):
            return type(a)(ServingEngine._pair_sites(x, y, fn)
                           for x, y in zip(a, b))
        return a

    def _sync_tables(self):
        """Push the host block table into every cache site (scan-stacked
        sites broadcast the same table across groups — page ids back the
        same token rows in every layer)."""
        self._table_uploads += 1
        with self._phase("tables"):
            bt = jnp.asarray(self._bt)

            def set_bt(site):
                cur = site["block_table"]
                new = bt if cur.ndim == 2 else \
                    jnp.broadcast_to(bt[None], cur.shape)
                return dict(site, block_table=new)

            self.caches = self._map_sites(self.caches, set_bt)

    def _fresh_stage(self, site, stage_len: int):
        cfg = self.model.cfg
        shape = (1, stage_len, cfg.n_kv_heads, cfg.head_dim)
        if site["block_table"].ndim == 3:
            shape = (site["block_table"].shape[0],) + shape
        z = jnp.zeros(shape, jnp.float32)
        return {"stage_k": z, "stage_v": z}

    def _emit_token(self, req: Request, tok: int, first: bool):
        """Record one sampled token into the current step's event buffer
        (call AFTER the request's done/finish_reason are settled)."""
        self._token_events.append(TokenEvent(
            uid=req.uid, token=tok, index=len(req.out_tokens) - 1,
            first=first, done=req.done, finish_reason=req.finish_reason))

    def _admit(self):
        if self.paged:
            self._admit_paged()
            return
        self._admit_slab()

    def _admit_slab(self):
        """Fill free slots from the queue (prefill batched per request).

        Prompts right-pad to the bucket length so the jit cache key (the
        bucket) matches the traced shape: every prompt length in a bucket
        reuses one trace. Next-token logits read at `length - 1`."""
        for s in range(self.cfg.batch_slots):
            # loop: a request finished by its own prefill token frees the
            # slot for the next queued request in the same admit pass
            while self.slots[s] is None and self.queue:
                req = self.queue.popleft()
                self._admitted_uids.append(req.uid)
                t = len(req.prompt)
                bucket = self._bucket(t) if self._bucket_ok else t
                toks = np.zeros((bucket,), np.int32)
                toks[:t] = req.prompt  # right-pad; causal mask shields pads
                fn = self._jit_prefill(bucket, self._prefill)
                # prefill into a fresh single-row cache, splice into slot s
                row_cache = self.model.init_caches(1, self.cfg.max_len,
                                                   dtype=jnp.float32)
                logits, row_cache = fn(
                    self.params, row_cache, jnp.asarray(toks[None, :]),
                    jnp.int32(t))
                self.caches = _splice_slot(self.caches, row_cache, s)
                self.pos[s] = t
                nxt = self._first_token(req, logits)
                finished = self._finish_at_admit(req, nxt)
                self._emit_token(req, nxt, first=True)
                if not finished:
                    self.slots[s] = req

    def _first_token(self, req: Request, logits) -> int:
        """Greedy first token from the prefill logits (1, vocab): a device
        sync, before the step's decode is dispatched."""
        with self._phase("first_token"):
            if self.cfg.keep_prefill_logits:
                req.prefill_logits = np.asarray(logits[0], np.float32)
            nxt = int(jnp.argmax(logits[0]))
        req.out_tokens.append(nxt)
        req.t_first = time.monotonic()
        return nxt

    def _finish_at_admit(self, req: Request, nxt: int) -> bool:
        """The prefill token already satisfies the budget (or hit EOS):
        never enter decode — a max_new_tokens=1 request must return
        exactly one token, not two."""
        if self.cfg.eos_id >= 0 and nxt == self.cfg.eos_id:
            req.finish_reason = "eos"
        elif len(req.out_tokens) >= req.max_new_tokens:
            req.finish_reason = "max_new_tokens"
        else:
            return False
        req.done = True
        req.t_done = time.monotonic()
        self.completed.append(req)
        return True

    def _admit_paged(self):
        """Reserve pages + a slot for queued requests and move them into
        the chunked-prefill pipeline. Admission is all-or-nothing on the
        request's WORST-CASE page budget (prompt stage + full decode
        horizon), so a running request can never OOM the pool mid-decode;
        FIFO order holds — a head-of-line request that doesn't fit blocks
        the queue until frees make room."""
        ps = self.pool.page_size
        for s in range(self.cfg.batch_slots):
            if not self.queue:
                return
            if self.slots[s] is not None or s in self._prefill_slots:
                continue
            req = self.queue[0]
            t = len(req.prompt)
            chunk = self.cfg.prefill_chunk
            chunk = -(-chunk // ps) * ps if chunk else 0
            stage_len = -(-self._bucket(t) // (chunk or ps)) * (chunk or ps)
            chunk = chunk or stage_len
            stage_tiles = stage_len // ps
            horizon = min(t + req.max_new_tokens, self.cfg.max_len)
            gen_pages = pages_for(horizon, ps)
            need = max(gen_pages, stage_tiles)
            got = self.pool.alloc(need, req.uid)
            if got is None:
                return
            self.queue.popleft()
            self._admitted_uids.append(req.uid)
            toks = np.zeros((stage_len,), np.int32)
            toks[:t] = req.prompt
            self._bt[s, :] = 0
            self._bt[s, :need] = got
            self._sync_tables()
            stage = self._map_sites(
                self.caches, lambda site: self._fresh_stage(site,
                                                            stage_len))
            self._prefilling.append(_Prefilling(
                req=req, slot=s, toks=toks, t=t, chunk=chunk,
                stage_len=stage_len, stage_tiles=stage_tiles, pages=got,
                gen_pages=gen_pages, target=-(-t // chunk) * chunk,
                written=0, stage=stage))
            self._prefill_slots.add(s)

    def _run_prefill_chunk(self):
        """Feed ONE chunk of the oldest mid-prefill request through the
        fused cache-write prefill — the per-step prefill budget that keeps
        long prompts from stalling the decode batch."""
        if not self._prefilling:
            return
        with self._phase("prefill_chunk"):
            pf, logits = self._prefill_one_chunk()
        if pf is None:
            return
        req, s = pf.req, pf.slot
        nxt = self._first_token(req, logits)
        finished = self._finish_at_admit(req, nxt)
        self._emit_token(req, nxt, first=True)
        if finished:
            self._free_slot_pages(s, req)
            return
        self.pos[s] = pf.t
        self.slots[s] = req

    def _prefill_one_chunk(self):
        """Dispatch the oldest mid-prefill request's next chunk and write
        its pages back. Returns (the request's `_Prefilling`, the chunk's
        logits) once its prompt is fully prefilled, else (None, None)."""
        pf = self._prefilling[0]
        off = pf.written
        toks = pf.toks[off:off + pf.chunk]
        positions = np.arange(off, off + pf.chunk, dtype=np.int32)
        bt_row = jnp.asarray(np.asarray(pf.pages[:pf.stage_tiles],
                                        np.int32)[None])

        def view(site, stage):
            btv = bt_row if site["block_table"].ndim == 2 else \
                jnp.broadcast_to(bt_row[None],
                                 (site["block_table"].shape[0],)
                                 + bt_row.shape)
            return dict(site, block_table=btv, **stage)

        caches_view = self._pair_sites(self.caches, pf.stage, view)
        fn = self._jit_prefill(("paged", pf.stage_len),
                               self._prefill_chunk)
        logits, new_view = fn(self.params, caches_view,
                              jnp.asarray(toks[None]),
                              jnp.asarray(positions[None]),
                              jnp.int32(pf.t - 1))
        self.prefill_chunks_run += 1
        # pool leaves mutated by the chunk write back into the live
        # caches NOW (decode steps of other slots interleave between
        # chunks); the raw stage persists on the request
        self.caches = self._pair_sites(
            self.caches, new_view,
            lambda site, new: dict(site, **{k: new[k] for k in site
                                            if k != "block_table"}))
        pf.stage = self._pair_sites(
            self.caches, new_view,
            lambda site, new: {"stage_k": new["stage_k"],
                               "stage_v": new["stage_v"]})
        pf.written += pf.chunk
        if pf.written < pf.target:
            return None, None
        # prompt fully prefilled: release the stage-only page surplus
        # (stage tiles past the decode horizon); the caller activates the
        # slot
        s = pf.slot
        self._prefilling.popleft()
        self._prefill_slots.discard(s)
        if len(pf.pages) > pf.gen_pages:
            self.pool.free(pf.req.uid, pf.pages[pf.gen_pages:])
        self._bt[s, :] = 0
        self._bt[s, :pf.gen_pages] = pf.pages[:pf.gen_pages]
        self._sync_tables()
        return pf, logits

    def _free_slot_pages(self, s: int, req: Request):
        self.pool.free(req.uid)
        self._bt[s, :] = 0
        self.pos[s] = self._pos_parked
        self._sync_tables()

    def _active(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is not None]

    @contextlib.contextmanager
    def _phase(self, name: str):
        """One host phase of a step: a `jax.profiler` span
        `engine.<name>`, on the device trace's clock when a profiler
        trace is on, and its host seconds added to the step's
        `StepEvents.phases`."""
        t = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("engine." + name):
                yield
        finally:
            self._phases[name] = (self._phases.get(name, 0.0)
                                  + time.perf_counter() - t)

    def step(self) -> StepEvents:
        """One engine iteration: admit, at most one prefill chunk (paged
        mode), then one batched decode step for every active slot.

        Returns the step's `StepEvents` — every token sampled this step
        (with its request attribution), admissions, and post-step
        queue/slot occupancy. Both serve loops (`run_until_drained` and
        the asyncio front end in `serve/frontend.py`) drive this one
        method and consume the events; nothing else mutates the engine.

        The step is the profiler span `engine.step` with `step_num` =
        `StepEvents.step`; its phases are the spans `engine.admit`,
        `engine.prefill_chunk`, `engine.first_token`, `engine.decode`,
        `engine.token_sync`, `engine.emit` and `engine.tables`
        (docs/serving.md).
        """
        with jax.profiler.StepTraceAnnotation("engine.step",
                                              step_num=self.steps_run):
            return self._step()

    def _step(self) -> StepEvents:
        t_start = time.monotonic()
        self._token_events = []
        self._admitted_uids = []
        self._phases = {}
        self._table_uploads = 0
        chunks_before = self.prefill_chunks_run
        with self._phase("admit"):
            self._admit()
        if self.paged:
            self._run_prefill_chunk()
        act = self._active()
        decode_batch = len(act)
        if act:
            with self._phase("decode"):
                tokens = np.zeros((self.cfg.batch_slots, 1), np.int32)
                for i in act:
                    tokens[i, 0] = self.slots[i].out_tokens[-1]
                logits, self.caches = self._decode(
                    self.params, self.caches, jnp.asarray(tokens),
                    jnp.asarray(self.pos))
            with self._phase("token_sync"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            with self._phase("emit"):
                self._emit_decoded(act, nxt)
        ev = StepEvents(
            step=self.steps_run, t_start=t_start, t_end=time.monotonic(),
            admitted=self._admitted_uids, prefill_chunks=(
                self.prefill_chunks_run - chunks_before),
            decode_batch=decode_batch, tokens=self._token_events,
            queue_depth=len(self.queue), active=len(self._active()),
            prefilling=len(self._prefilling) if self.paged else 0,
            phases=self._phases, table_uploads=self._table_uploads)
        self.steps_run += 1
        return ev

    def _emit_decoded(self, act: List[int], nxt: np.ndarray):
        """Append each active slot's sampled token, finish the requests
        that stop on it (freeing their slots and pages), and record the
        step's token events."""
        for i in act:
            req = self.slots[i]
            self.pos[i] += 1
            tok = int(nxt[i])
            req.out_tokens.append(tok)
            if self.cfg.eos_id >= 0 and tok == self.cfg.eos_id:
                reason = "eos"
            elif len(req.out_tokens) >= req.max_new_tokens:
                reason = "max_new_tokens"
            elif int(self.pos[i]) >= self.cfg.max_len - 1:
                # out of cache rows before the token budget: surface
                # the truncation instead of silently stopping early
                reason = "length_cap"
            else:
                self._emit_token(req, tok, first=False)
                continue
            req.done = True
            req.finish_reason = reason
            req.t_done = time.monotonic()
            self.completed.append(req)
            self.slots[i] = None
            if self.paged:
                self._free_slot_pages(i, req)
            self._emit_token(req, tok, first=False)

    def has_work(self) -> bool:
        """True while a `step()` could make progress: requests queued,
        decoding, or mid-chunked-prefill. Both serve loops poll this."""
        return bool(self.queue or self._active()
                    or (self.paged and self._prefilling))

    def run_until_drained(self, max_steps: int = 10000, metrics=None):
        """Synchronous batch loop: step until no request is queued,
        prefilling, or decoding. `metrics` (a
        `serve.metrics.MetricsLedger`) records every step's events —
        the same ledger the async front end feeds, so drained-loop
        benchmarks and async serves produce comparable traces."""
        steps = 0
        while self.has_work() and steps < max_steps:
            ev = self.step()
            if metrics is not None:
                metrics.on_step(ev, self)
            steps += 1
        return self.completed

    # ------------------------------------------------------ observability
    def stats(self) -> Dict[str, object]:
        """Engine counters: prefill trace/cache behaviour, chunk counts,
        steps run, and (paged mode) the page pool's occupancy/failure
        stats.

        COUNTER SEMANTICS — every scalar here is a LIFETIME counter:
        monotone non-decreasing since engine construction, never reset by
        `step()` or `run_until_drained()` (two drained runs on one engine
        accumulate). Per-step numbers come from the `StepEvents` that
        `step()` returns, or from a `serve.metrics.MetricsLedger` fed
        with them; `prefill_cache_size` and the pool's
        `used_pages`/`free_pages`/`occupancy` are instantaneous gauges,
        while the pool's `allocs`/`frees`/`alloc_failures`/`peak_used`
        are lifetime too. `tests/test_serve_frontend.py` pins this
        contract.
        """
        st: Dict[str, object] = {
            "steps_run": self.steps_run,
            "prefill_traces": self.prefill_traces,
            "prefill_cache_size": len(self._prefill_cache),
            "prefill_cache_evictions": self.prefill_cache_evictions,
            "prefill_chunks_run": self.prefill_chunks_run,
        }
        if self.paged:
            st["page_pool"] = self.pool.stats()
        return st

    def device_pool_stats(self) -> Dict[str, object]:
        """Per-device KV-pool footprint (paged mode).

        Under an installed mesh the sharded backends split every pool
        data/scale leaf along `Hkv` over the "model" axis, so each device
        holds `1/model` of the pool bytes; block tables replicate (they
        are bytes-negligible index arrays). Without a mesh this degrades
        to the single-device view (`n_devices=1`). Occupancy is the SAME
        gauge on every shard — pages allocate globally, shards differ
        only in which heads of a page they hold — so the per-device list
        repeats the pool's occupancy once per model-axis shard.
        """
        if not self.paged:
            return {"n_devices": 1, "pool_bytes_total": 0,
                    "pool_bytes_per_device": 0,
                    "occupancy_per_device": []}
        occ = self.device_pool_occupancy()
        tp = len(occ)
        total = 0
        flat = jax.tree_util.tree_flatten_with_path(self.caches)[0]
        for kp, leaf in flat:
            name = str(getattr(kp[-1], "key", getattr(kp[-1], "idx",
                                                      kp[-1])))
            if name in ("k", "v", "k_data", "v_data", "k_scl", "v_scl",
                        "stage_k", "stage_v"):
                total += int(leaf.size * leaf.dtype.itemsize)
        return {"n_devices": tp,
                "pool_bytes_total": int(total),
                "pool_bytes_per_device": int(total // tp),
                "occupancy_per_device": occ}

    def device_pool_occupancy(self) -> List[float]:
        """The pool's occupancy once per "model"-axis shard of the
        installed mesh (`[occupancy]` without one; paged mode): the
        per-step gauge of `device_pool_stats`, without its walk over the
        cache pytree."""
        mesh = backends.current_mesh()
        tp = 1
        if mesh is not None:
            from repro.sharding.rules import mesh_axis_sizes
            tp = mesh_axis_sizes(mesh).get("model", 1) or 1
        return [float(self.pool.occupancy())] * int(tp)

    def defrag(self):
        """Compact live pages onto the low end of the pool (paged mode):
        gathers every site's pool arrays by the compaction source map and
        rebuilds the block tables. Serving results are unchanged — pages
        are position-independent — so this exists for pool elasticity
        (the free tail can be released), not correctness."""
        if not self.paged:
            return None
        src, remap = self.pool.compact()
        srcj = jnp.asarray(src)

        def gather(site):
            out = {}
            for k, v in site.items():
                if k == "block_table":
                    out[k] = v
                else:
                    out[k] = v[srcj] if site["block_table"].ndim == 2 \
                        else v[:, srcj]
            return out

        self.caches = self._map_sites(self.caches, gather)
        self._bt[:] = 0
        owners = {r.uid: (s, r) for s, r in enumerate(self.slots)
                  if r is not None}
        for pf in self._prefilling:
            pf.pages = self.pool.pages_of(pf.req.uid)
            self._bt[pf.slot, :len(pf.pages)] = pf.pages
        for uid, (s, _r) in owners.items():
            pages = self.pool.pages_of(uid)
            self._bt[s, :len(pages)] = pages
        self._sync_tables()
        return remap


def _splice_slot(full_caches, row_caches, slot: int):
    """Copy a 1-row cache pytree into row `slot` of the batched caches.

    Batch is the first dim of unstacked leaves and the second of scan-
    stacked leaves (leading group dim) — detected by matching shapes.
    """
    def splice(full, row):
        if full.shape == row.shape:
            return row
        # find the axis where row has size 1 and full has batch_slots
        for ax in range(row.ndim):
            if row.shape[ax] == 1 and full.shape[ax] != 1 and \
                    row.shape[:ax] == full.shape[:ax] and \
                    row.shape[ax + 1:] == full.shape[ax + 1:]:
                idx = [slice(None)] * full.ndim
                idx[ax] = slice(slot, slot + 1)
                return full.at[tuple(idx)].set(row.astype(full.dtype))
        # silently keeping `full` here would drop the prefilled row and
        # serve the request on a stale cache — fail loudly instead
        raise ValueError(
            f"_splice_slot: cannot splice row cache of shape {row.shape} "
            f"into batched cache of shape {full.shape}: no axis has "
            f"size 1 in the row and the slot count in the batch")

    return jax.tree_util.tree_map(splice, full_caches, row_caches)
