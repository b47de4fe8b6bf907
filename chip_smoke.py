"""Serve qwen1.5-0.5b at full width on one TPU chip through the fused OVP
Pallas kernels, and check what comes out.

    python chip_smoke.py               # one chip: the served path
    python chip_smoke.py --four-chips  # a 2x2 host: the sharded path only

One process drives the chip(s) from start to end and starts no other. The
model is built from a seed (random weights) at the published widths of
`src/repro/configs/qwen1_5_0_5b.py`, quantized by the `olive_serve` preset
as that preset defines it (bf16 compute, W4A4 at dynamic activation
scales, 4-bit OVP KV cache), and served through the helpers the serving
CLI (`repro.launch.serve`) uses: `--backend pallas`, a paged cache of
16-token pages, chunked prefill of 128 tokens, 8 slots, max_len 1024.

Checks, each of which fails the run:
  - every request finished with its 32 new tokens;
  - `backends.dispatch_stats()` holds no decline key (`->`), and the
    matmul, decode-attention and prefill-attention kernels all served;
  - no backend in use runs the Pallas interpreter;
  - each quantized matmul of the model, at its real shape, gives the
    `reference` backend's output (plain jnp, float32) on the same W4A4
    codes, within MATMUL_REL_TOL;
  - the prefill logits of the longest prompt on the `pallas` path agree
    with the `reference` backend on the same quantized weights and KV
    cache, within LOGITS_REL_TOL (see there for why this comparison
    runs the weight-only program).
With `--four-chips`: the same requests served through `--mesh 1,4` on
`pallas_sharded` with no decline of any kind, and, for the comparison,
the same greedy tokens as the one-chip `pallas` run on device 0.

The wall seconds printed on the way are set-up times of this run (they
include compilation), not performance metrics. The last line of stdout is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
With no TPU, with the `pallas` backends registered as the interpreter
(REPRO_FORCE_INTERPRET), without the repo's sources
beside this file, or with any failed check, the script exits non-zero and
never prints `"ok": true`.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "qwen1.5-0.5b"
QUANT = "olive_serve"
SLOTS, MAX_LEN, PAGE, CHUNK = 8, 1024, 16, 128
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, (64, 513), 32
# Relative L2 distances allowed against the reference, both sides in
# float32 at full matmul precision.
#
# W4A4 makes the forward discontinuous: a perturbation that moves an
# activation across an int4 rounding boundary changes its code by a whole
# step, and such flips compound over the layers. Two correct
# implementations that only sum in another order therefore drift apart
# with depth: pallas vs reference on the W4A4 program gives 0.27 on a CPU
# (4-layer cut, full width, 451-token prompt) and 0.68 on a v5e (all 24
# layers) — as far apart as quantizing activations at all moves the
# logits (0.46, W4A4 vs W4 on the 4-layer cut). So the model-level check
# runs the weight-only program (same W4 weights, same 4-bit OVP KV cache,
# activations unquantized), whose forward is continuous: 1.2e-6 on the
# CPU cut. The W4A4 activation prologue is checked matmul by matmul
# instead, where the kernel and the reference see the same codes and only
# the summation order differs. A kernel that mis-pairs lanes, drops a
# scale or reads the wrong page lands near 1 on either check.
LOGITS_REL_TOL = 1e-3
MATMUL_REL_TOL = 1e-4


class SmokeError(RuntimeError):
    """A check of the smoke failed."""


def decline_keys(stats) -> list:
    """The `dispatch_stats()` keys that record a decline (a fallback)."""
    return sorted(k for k in stats if "->" in k)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _import_repro():
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise SmokeError(f"no repro package under {src}: run this script "
                         f"from a checkout of the repository")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from repro import backends
    from repro.launch import serve
    return backends, serve


def build(arch: str, backend: str, seed: int):
    """(cfg, model, quantized params, {phase: wall seconds})."""
    import jax
    backends, serve = _import_repro()
    from repro.configs import get_config
    from repro.core.qlinear import quantize_params
    cfg = get_config(arch)
    policy = serve.build_policy(cfg, QUANT, backend=backend)
    walls = {}
    t = time.perf_counter()
    model, params = serve.init_model(cfg, policy, seed)
    jax.block_until_ready(params)
    walls["init"] = time.perf_counter() - t
    t = time.perf_counter()
    qparams = quantize_params(params, policy)
    jax.block_until_ready(qparams)
    walls["ptq"] = time.perf_counter() - t
    return cfg, model, qparams, walls


def serve_requests(model, params, prompts, *, backend: str, mesh=None,
                   slots: int = SLOTS, max_len: int = MAX_LEN,
                   max_new: int = MAX_NEW, keep_logits: bool = False):
    """Serve `prompts` through the paged engine; returns (completed
    requests in submission order, dispatch stats of this serve, wall
    seconds of the first engine step, wall seconds of the rest)."""
    backends, serve = _import_repro()
    backends.reset_dispatch_stats()
    eng = serve.make_engine(model, params, slots=slots, max_len=max_len,
                            page_size=PAGE, prefill_chunk=CHUNK, mesh=mesh,
                            backend=backend,
                            keep_prefill_logits=keep_logits)
    uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    t = time.perf_counter()
    eng.step()
    first = time.perf_counter() - t
    t = time.perf_counter()
    done = {r.uid: r for r in eng.run_until_drained()}
    rest = time.perf_counter() - t
    _check(sorted(done) == sorted(uids),
           f"{backend}: {len(done)} of {len(uids)} requests finished")
    reqs = [done[u] for u in uids]
    for r in reqs:
        _check(len(r.out_tokens) == max_new
               and r.finish_reason == "max_new_tokens",
               f"{backend}: request {r.uid} stopped after "
               f"{len(r.out_tokens)} tokens ({r.finish_reason})")
    return reqs, backends.dispatch_stats(), first, rest


def check_dispatch(stats, backend: str) -> None:
    """No decline anywhere, and every kernel family served on `backend`."""
    _check(not decline_keys(stats),
           f"declined dispatches: {decline_keys(stats)}")
    for key in (backend, f"{backend}[decode_attn]",
                f"{backend}[prefill_attn]"):
        _check(stats.get(key, 0) > 0, f"no dispatch recorded under {key!r}"
                                      f" (stats: {stats})")


def f32_policy(cfg, backend: str, abits: int):
    """The served program (same weight and KV quantization, same params
    layout) in float32 compute, with `abits`-bit activations."""
    _, serve = _import_repro()
    return serve.build_policy(cfg, QUANT, backend=backend).replace_all(
        compute_dtype="float32", abits=abits)


def f32_model(cfg, backend: str, abits: int = 0):
    """The model of `f32_policy`: the comparison twin of the served one."""
    from repro.models.model import build_model
    return build_model(cfg, f32_policy(cfg, backend, abits), remat=False)


def layer0_matmuls(qparams) -> dict:
    """{site: QuantizedTensor} of the first layer's quantized matmuls
    (the scan stacks layers on axis 0)."""
    import jax
    from repro.core.ovp import QuantizedTensor
    out = {}
    flat = jax.tree_util.tree_flatten_with_path(
        qparams, is_leaf=lambda x: isinstance(x, QuantizedTensor))[0]
    for path, leaf in flat:
        if isinstance(leaf, QuantizedTensor) and leaf.data.ndim == 3:
            site = "/".join(str(getattr(k, "key", k)) for k in path)
            out[site] = jax.tree_util.tree_map(lambda a: a[0], leaf)
    return out


def matmul_errors(cfg, qparams, backend: str, reference: str, seed: int,
                  rows: int = CHUNK) -> dict:
    """{site: relative L2 error} of `backend` against `reference` on each
    layer-0 matmul of the W4A4 program, at float32 and full precision
    (for a sharded backend: column- and row-parallel sites alike).
    The activations are Gaussian with 1% outliers at 20x, so the OVP
    outlier path runs."""
    import jax
    import jax.numpy as jnp
    backends, _ = _import_repro()
    pol = f32_policy(cfg, backend, abits=4)
    ref = f32_policy(cfg, reference, abits=4)
    errs = {}
    key = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision("highest"):
        for site, w in layer0_matmuls(qparams).items():
            key, kx, ko = jax.random.split(key, 3)
            shape = (rows, w.orig_dim)
            x = jax.random.normal(kx, shape) * jnp.where(
                jax.random.uniform(ko, shape) < 0.01, 20.0, 1.0)
            got = backends.get_backend(backend).matmul(
                x, w, pol.resolve(site), site=site)
            want = backends.get_backend(reference).matmul(
                x, w, ref.resolve(site), site=site)
            errs[site] = logits_rel_err(got, want)
    return errs


def logits_rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def run_one_chip(arch: str = ARCH, backend: str = "pallas",
                 reference: str = "reference", seed: int = 0,
                 n_requests: int = N_REQUESTS, prompt_len=PROMPT_LEN,
                 max_new: int = MAX_NEW, slots: int = SLOTS,
                 max_len: int = MAX_LEN) -> dict:
    _, serve = _import_repro()
    cfg, model, qparams, walls = build(arch, backend, seed)
    _log(f"{arch}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
         f"{cfg.n_heads} heads ({cfg.n_kv_heads} kv), head_dim "
         f"{cfg.head_dim}, vocab {cfg.vocab}; preset {QUANT} on {backend}")
    prompts = serve.make_prompts(cfg.vocab, n_requests, *prompt_len, seed)
    reqs, stats, walls["first_step"], walls["serve"] = serve_requests(
        model, qparams, prompts, backend=backend, slots=slots,
        max_len=max_len, max_new=max_new)
    check_dispatch(stats, backend)
    _log(f"dispatch stats: {stats}")
    n_prompt = sum(len(p) for p in prompts)
    n_new = sum(len(r.out_tokens) for r in reqs)
    _log(f"tokens: {len(reqs)} requests, {n_prompt} prompt tokens "
         f"(lengths {[len(p) for p in prompts]}), {n_new} generated")

    t = time.perf_counter()
    errs = matmul_errors(cfg, qparams, backend, reference, seed)
    walls["matmul_check"] = time.perf_counter() - t
    _log(f"W4A4 matmuls, {backend} vs {reference} at float32, relative L2 "
         f"error per site (bound {MATMUL_REL_TOL}): {errs}")
    _check(max(errs.values()) <= MATMUL_REL_TOL,
           f"a W4A4 matmul differs from the reference: {errs}")

    # the model-level check: weight-only program in float32 (see
    # LOGITS_REL_TOL); the longest prompt crosses the most prefill chunks
    import jax
    i = max(range(len(prompts)), key=lambda j: len(prompts[j]))
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        got, got_stats, _, _ = serve_requests(
            f32_model(cfg, backend), qparams, [prompts[i]], backend=backend,
            slots=1, max_len=max_len, max_new=1, keep_logits=True)
        _check(not decline_keys(got_stats),
               f"declined dispatches: {decline_keys(got_stats)}")
        ref, _, _, _ = serve_requests(
            f32_model(cfg, reference), qparams, [prompts[i]], backend=reference,
            slots=1, max_len=max_len, max_new=1, keep_logits=True)
    walls["logits_check"] = time.perf_counter() - t
    want = ref[0].prefill_logits[:cfg.vocab]    # pad columns are masked
    err = logits_rel_err(got[0].prefill_logits[:cfg.vocab], want)
    _log(f"prefill logits, prompt of {len(prompts[i])} tokens, W4 weights "
         f"+ 4-bit KV in float32: relative L2 error {backend} vs "
         f"{reference} {err!r} (bound {LOGITS_REL_TOL})")
    _check(err <= LOGITS_REL_TOL,
           f"prefill logits differ from the reference: relative L2 error "
           f"{err} > {LOGITS_REL_TOL}")
    _log("set-up wall seconds of this run (not metrics): "
         + ", ".join(f"{k} {v:.3f}" for k, v in walls.items()))
    return {"walls": walls, "logits_rel_err": err, "stats": stats}


def run_four_chips(arch: str = ARCH, backend: str = "pallas",
                   sharded: str = "pallas_sharded", seed: int = 0,
                   n_requests: int = N_REQUESTS, prompt_len=PROMPT_LEN,
                   max_new: int = MAX_NEW, slots: int = SLOTS,
                   max_len: int = MAX_LEN) -> dict:
    """The requests served through a 1x4 mesh on `sharded` as the preset
    defines them, then compared with one chip (`backend`, device 0).

    The token comparison runs the weight-only program in float32 at full
    precision: the row-parallel sites sum their K shards in another order
    than one kernel does, which on the W4A4 program flips activation codes
    and then tokens (see LOGITS_REL_TOL). The sharded W4A4 matmuls are
    compared one by one instead, column- and row-parallel."""
    import jax
    _, serve = _import_repro()
    _check(jax.device_count() >= 4,
           f"--four-chips needs 4 devices, have {jax.device_count()}")
    cfg, model, qparams, walls = build(arch, sharded, seed)
    prompts = serve.make_prompts(cfg.vocab, n_requests, *prompt_len, seed)
    mesh = serve.parse_mesh("1,4")
    served, stats, _, walls["serve_four_chips"] = serve_requests(
        model, qparams, prompts, backend=sharded, mesh=mesh, slots=slots,
        max_len=max_len, max_new=max_new)
    _log(f"served {len(served)} requests on mesh 1x4 ({QUANT}); dispatch "
         f"stats: {stats}")
    check_dispatch(stats, sharded)

    errs = matmul_errors(cfg, qparams, sharded, backend, seed)
    _log(f"W4A4 matmuls, {sharded} vs {backend} at float32, relative L2 "
         f"error per site (bound {MATMUL_REL_TOL}): {errs}")
    _check(max(errs.values()) <= MATMUL_REL_TOL,
           f"a sharded W4A4 matmul differs from one chip: {errs}")

    with jax.default_matmul_precision("highest"):
        one, stats1, _, walls["compare_one_chip"] = serve_requests(
            f32_model(cfg, backend), qparams, prompts, backend=backend,
            slots=slots, max_len=max_len, max_new=max_new)
        check_dispatch(stats1, backend)
        four, stats4, _, walls["compare_four_chips"] = serve_requests(
            f32_model(cfg, sharded), qparams, prompts, backend=sharded,
            mesh=mesh, slots=slots, max_len=max_len, max_new=max_new)
        check_dispatch(stats4, sharded)
    diff = [r1.uid for r1, r4 in zip(one, four)
            if r1.out_tokens != r4.out_tokens]
    _log(f"greedy tokens, W4 weights + 4-bit KV in float32, {sharded} on "
         f"mesh 1x4 vs {backend} on one chip: {len(one) - len(diff)} of "
         f"{len(one)} requests identical "
         f"({sum(len(r.out_tokens) for r in four)} tokens)")
    _check(not diff, f"greedy tokens differ for requests {diff}")
    _log("set-up wall seconds of this run (not metrics): "
         + ", ".join(f"{k} {v:.3f}" for k, v in walls.items()))
    return {"walls": walls, "stats": stats4, "matmul_errs": errs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a mesh of 4 chips "
                         "(--mesh 1,4, pallas_sharded) and the one-chip "
                         "run it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        backends, serve = _import_repro()
        for name in ("pallas", "pallas_sharded"):
            # REPRO_FORCE_INTERPRET re-registers these as the interpreter
            _check(not backends.get_backend(name).interpret,
                   f"backend {name} runs the Pallas interpreter "
                   f"(REPRO_FORCE_INTERPRET is set?)")
        import jax
        devices = jax.devices()
        dev = devices[0]
        _check(dev.platform == "tpu",
               f"JAX finds no TPU (platform {dev.platform!r}); the smoke "
               f"never runs on the CPU")
        cache = serve.configure_compile_cache()
        _log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
             f"compile cache {cache}")
        if args.four_chips:
            run_four_chips(seed=args.seed)
        else:
            run_one_chip(seed=args.seed)
    except SmokeError as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
