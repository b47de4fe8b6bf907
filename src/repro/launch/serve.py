"""Production serving launcher.

Loads (or trains a throwaway) model for --arch, applies the OliVe PTQ
policy — a flat preset, a named mixed-precision *policy program* preset
(`olive_mixed_w48`, `olive_owq_style`), and/or ad-hoc site rules — and
runs the continuous-batching engine on a synthetic request stream.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b-smoke \
      --quant olive_serve --requests 16
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b-smoke \
      --quant olive_mixed_w48 \
      --policy-rules "layers/1/mlp/*=olive_w8a8" --requests 16

Static calibrated activation scales (docs/calibration.md) — one command
calibrates on a synthetic batch, saves the artifact, and serves on it:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b-smoke \
      --quant olive_w4a4 --calibrate --calibration /tmp/calib.json \
      --requests 8

Re-serving from a saved artifact skips the calibration pass:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b-smoke \
      --quant olive_w4a4 --calibration /tmp/calib.json --requests 8

Paged KV cache (docs/kv_cache.md) — block-table page pool instead of the
(slots, max_len) slab, fused cache-write prefill, optional chunked
prefill interleaved with decode:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b-smoke \
      --quant olive_serve --paged 16 --prefill-chunk 32 --requests 16

Async streaming serve (docs/serving.md) — the asyncio front end drives
the same engine step: per-request token streams (`--stream` prints each
token the step it is sampled), TTFT/TPOT SLO metrics per step, and a
JSONL metrics trace the benchmarks consume:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b-smoke \
      --quant olive_serve --paged 16 --prefill-chunk 32 --requests 16 \
      --async --stream --metrics-out /tmp/serve_trace.jsonl

Presets are served as named (olive_serve: bf16 compute, W4A4 at dynamic
activation scales, 4-bit OVP KV cache). On a CPU, pick
`--backend pallas_interpret` (the kernels under the Pallas interpreter) or
`--backend xla`; `chip_smoke.py` at the repo root drives the same helpers
on a TPU.

The helpers below (`build_policy`, `init_model`, `make_engine`,
`make_prompts`) are the whole set-up path; `main` and `chip_smoke.py` both
call them, so the smoke serves exactly what this CLI serves.
"""
from __future__ import annotations

import argparse
import asyncio
import os
import time
from pathlib import Path
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import backends
from repro.configs import get_config
from repro.core.calibration import (CalibrationArtifact, apply_calibration,
                                    calibrate_model)
from repro.core.policy import (PRESETS, PROGRAM_PRESETS, get_policy,
                               get_program, parse_rules)
from repro.core.qlinear import quantize_params
from repro.models.model import build_model
from repro.serve.engine import EngineCfg, Request, ServingEngine
from repro.serve.frontend import AsyncFrontend
from repro.serve.metrics import MetricsLedger
from repro.serve.paging import PagePoolCfg

REPO_ROOT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache lives at the fixed
    `.jax_cache/` of this checkout (git-ignored): the path is part of the
    cache key, so a moving directory would never hit. Called by entry
    points only, never at import time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_policy(cfg, quant: str, *, policy_rules: Optional[str] = None,
                 backend: Optional[str] = None, calibration: bool = False):
    """The preset (or policy program) `quant` as named, with the
    `--policy-rules` overlay and the backend override. A calibration
    artifact switches every site to static activation scales."""
    if quant in PROGRAM_PRESETS or policy_rules:
        policy = get_program(None if quant == "fp" else quant,
                             n_layers=cfg.n_layers)
        if policy_rules:
            policy = policy.with_rules(parse_rules(policy_rules))
    else:
        policy = get_policy(None if quant == "fp" else quant)
    if calibration:
        policy = policy.replace_all(act_scale_mode="static")
    if backend is not None:
        policy = policy.with_backend(backend)
    return policy


def init_model(cfg, policy, seed: int = 0):
    """(model, f32 master params) drawn from `seed`."""
    model = build_model(cfg, policy, remat=False)
    return model, model.init(jax.random.PRNGKey(seed), dtype=jnp.float32)


def parse_mesh(spec: str):
    """'DATA,MODEL' -> a `MeshPlan` over the first DATA*MODEL devices."""
    from repro.runtime.elastic import MeshPlan
    sizes = tuple(int(v) for v in spec.split(","))
    if len(sizes) != 2 or any(v < 1 for v in sizes):
        raise ValueError(f"--mesh wants two positive sizes 'data,model', "
                         f"got {spec!r}")
    if sizes[0] * sizes[1] > jax.device_count():
        raise ValueError(f"--mesh {spec} needs {sizes[0] * sizes[1]} "
                         f"devices, have {jax.device_count()}")
    return MeshPlan(shape=sizes, axis_names=("data", "model"),
                    dropped_devices=0)


def make_engine(model, params, *, slots: int, max_len: int,
                page_size: int = 0, prefill_chunk: int = 0, mesh=None,
                backend: Optional[str] = None,
                keep_prefill_logits: bool = False) -> ServingEngine:
    """The continuous-batching engine: slab cache, or a paged pool of
    `page_size` pages with chunked prefill."""
    page_pool = PagePoolCfg(page_size=page_size) if page_size else None
    return ServingEngine(model, params, EngineCfg(
        batch_slots=slots, max_len=max_len, page_pool=page_pool,
        prefill_chunk=prefill_chunk, mesh=mesh, backend=backend,
        keep_prefill_logits=keep_prefill_logits))


def make_prompts(vocab: int, n: int, min_len: int, max_len: int,
                 seed: int = 0) -> List[np.ndarray]:
    """`n` seeded prompts of uniform random tokens, lengths drawn from
    [min_len, max_len)."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=int(rng.integers(min_len, max_len)))
            .astype(np.int32) for _ in range(n)]


async def _serve_async(eng, prompts, max_new, metrics, stream_tokens):
    """Drive the engine through the asyncio streaming front end: submit
    every prompt, consume each token stream as tokens arrive (printing
    per token when --stream), drain, and return the completed requests
    in the same token-for-token order the drained loop would produce."""

    async def consume(stream):
        seen = 0
        async for tok in stream:
            if stream_tokens:
                tag = "first" if seen == 0 else f"+{seen}"
                print(f"[stream] uid={stream.uid} {tag} token={tok}")
            seen += 1
        if stream_tokens:
            print(f"[stream] uid={stream.uid} done "
                  f"({len(stream.tokens)} tokens, {stream.finish_reason})")

    async with AsyncFrontend(eng, metrics=metrics) as fe:
        streams = [fe.submit(p, max_new_tokens=max_new) for p in prompts]
        await asyncio.gather(*(consume(s) for s in streams))
    return list(eng.completed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--quant", default="olive_w4",
                    choices=sorted(PRESETS) + sorted(PROGRAM_PRESETS)
                    + ["fp"],
                    help="PTQ policy or policy-program preset for the "
                         "weights/KV")
    ap.add_argument("--policy-rules", default=None,
                    help="extra site rules prepended to the program, "
                         "e.g. 'layers/0/*=olive_w8a8,*mlp*=olive_w4a4' "
                         "(see docs/policies.md)")
    ap.add_argument("--backend", default=None,
                    choices=backends.available(),
                    help="quantized-matmul execution backend "
                         "(default: the policy's; CPU smoke runs can use "
                         "pallas_interpret to exercise the fused kernel)")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="CalibrationArtifact JSON: serve with static "
                         "calibrated activation scales "
                         "(act_scale_mode='static' on every quantized "
                         "site; see docs/calibration.md)")
    ap.add_argument("--calibrate", action="store_true",
                    help="calibrate-then-serve: run the §3.4 calibration "
                         "pass on a synthetic batch first, save the "
                         "artifact to --calibration PATH, then serve on "
                         "it (one command end to end)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--paged", type=int, default=0, metavar="PAGE_SIZE",
                    help="serve on the paged KV cache: a block-table "
                         "page pool with this page size instead of the "
                         "(slots, max_len) slab; prefill writes pages "
                         "through the fused cache-write kernel (see "
                         "docs/kv_cache.md)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="paged mode: split long prompts into chunks of "
                         "this many tokens, interleaved with decode "
                         "steps (at most one chunk per step)")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="serve through the asyncio streaming front end "
                         "(serve/frontend.py): continuous intake, "
                         "per-request token streams, step-level TTFT/"
                         "TPOT SLO metrics (see docs/serving.md)")
    ap.add_argument("--stream", action="store_true",
                    help="async mode: print every token the step it is "
                         "sampled (one line per request completion too)")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="comma-separated mesh axis sizes for the "
                         "sharded backends, e.g. '4,2' for a "
                         "(data=4, model=2) mesh over 8 devices "
                         "(XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 forces logical CPU devices). Installs "
                         "the mesh via backends.configure_mesh so "
                         "--backend pallas_sharded[_interpret] "
                         "tensor/expert/KV-shards the quantized serve "
                         "path (see docs/sharding.md)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the step/request JSONL metrics trace "
                         "(serve/metrics.py vocabulary) to PATH; works "
                         "in both the drained loop and --async mode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.calibrate and not args.calibration:
        ap.error("--calibrate needs --calibration PATH to save into")
    if args.prefill_chunk and not args.paged:
        ap.error("--prefill-chunk requires --paged (chunked prefill is "
                 "a paged-cache feature)")
    if args.stream and not args.use_async:
        ap.error("--stream requires --async (the drained loop has no "
                 "token streams)")

    configure_compile_cache()
    cfg = get_config(args.arch)
    policy = build_policy(cfg, args.quant, policy_rules=args.policy_rules,
                          backend=args.backend,
                          calibration=bool(args.calibration))
    print(f"[serve] quantized-matmul backend(s): "
          f"{', '.join(sorted(policy.backends()))}")
    model, params = init_model(cfg, policy, args.seed)

    if args.calibration:
        if args.calibrate:
            rng = np.random.default_rng(args.seed)
            batch = {"tokens": jnp.asarray(rng.integers(
                0, cfg.vocab, size=(2, 64)).astype(np.int32))}
            t0 = time.time()
            artifact = calibrate_model(model, params, [batch])
            artifact.save(args.calibration)
            print(f"[serve] calibrated {len(artifact.sites())} sites in "
                  f"{time.time()-t0:.1f}s -> {args.calibration}")
        else:
            if not os.path.exists(args.calibration):
                ap.error(f"--calibration {args.calibration} does not "
                         f"exist; pass --calibrate to create it")
            artifact = CalibrationArtifact.load(args.calibration)
            print(f"[serve] loaded {len(artifact.sites())} static scales "
                  f"from {args.calibration}")
        policy = apply_calibration(policy, artifact)
        # per-layer scale rules address layers/<i>: rebuild so the model
        # unrolls to the layout the scales were calibrated on
        model = build_model(cfg, policy, remat=False)
        params = model.adapt_params(params)

    if policy.enabled:
        t0 = time.time()
        params = quantize_params(params, policy)
        print(f"[serve] PTQ ({args.quant}) in {time.time()-t0:.1f}s")

    mesh_plan = None
    if args.mesh:
        try:
            mesh_plan = parse_mesh(args.mesh)
        except ValueError as e:
            ap.error(f"{e} (on a CPU, set XLA_FLAGS=--xla_force_host_"
                     f"platform_device_count=N before launch)")
        print(f"[serve] mesh: data={mesh_plan.shape[0]} "
              f"model={mesh_plan.shape[1]} over {jax.device_count()} "
              f"devices")

    eng = make_engine(model, params, slots=args.slots, max_len=args.max_len,
                      page_size=args.paged, prefill_chunk=args.prefill_chunk,
                      mesh=mesh_plan)
    prompts = make_prompts(cfg.vocab, args.requests, 4, 32, args.seed)
    metrics = MetricsLedger() if (args.metrics_out or args.use_async) \
        else None
    t0 = time.time()
    if args.use_async:
        done = asyncio.run(_serve_async(eng, prompts, args.max_new,
                                        metrics, args.stream))
    else:
        for p in prompts:
            eng.submit(p, max_new_tokens=args.max_new)
        done = eng.run_until_drained(metrics=metrics)
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    lat = [r.t_done - r.t_submit for r in done]
    ttft = [r.t_first - r.t_submit for r in done if r.t_first]
    print(f"[serve] {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s)")
    # latency and TTFT are independent metrics: an empty TTFT list (no
    # request ever recorded a first token) must not suppress the latency
    # line, so they print separately
    if lat:
        print(f"[serve] mean latency {np.mean(lat)*1e3:.0f} ms")
    if ttft:
        print(f"[serve] mean TTFT {np.mean(ttft)*1e3:.0f} ms")
    dec_stats = {k: v for k, v in backends.dispatch_stats().items()
                 if "[decode_attn]" in k or "[prefill_attn]" in k}
    if dec_stats:
        # which backend served each attention path per traced site — on
        # the pallas backends a packed KV cache must show zero fallbacks
        # (no full-cache dequant per step; see docs/kv_cache.md)
        print(f"[serve] attention dispatch: {dec_stats}")
    if args.paged:
        st = eng.stats()
        print(f"[serve] page pool: {st['page_pool']} "
              f"(prefill chunks: {st['prefill_chunks_run']})")
    if args.calibration:
        # the whole point of static serving: zero dynamic resolutions
        print(f"[serve] act-scale resolutions: {backends.act_scale_stats()}")
    if metrics is not None:
        snap = metrics.snapshot()

        def _fmt(d):
            if not d.get("n"):
                return "n=0"
            return (f"n={d['n']} mean={d['mean']*1e3:.1f}ms "
                    f"p50={d['p50']*1e3:.1f}ms p95={d['p95']*1e3:.1f}ms")

        print(f"[serve] SLO: TTFT {_fmt(snap['ttft_s'])} | "
              f"TPOT {_fmt(snap['tpot_s'])} | ITL {_fmt(snap['itl_s'])}")
        print(f"[serve] {snap['steps']} steps, fallbacks={snap['fallbacks']}"
              + (f", interleave={snap['prefill_interleave_ratio']:.2f}"
                 if snap["prefill_interleave_ratio"] is not None else ""))
        if args.metrics_out:
            metrics.write_jsonl(args.metrics_out)
            print(f"[serve] metrics trace -> {args.metrics_out}")


if __name__ == "__main__":
    main()
