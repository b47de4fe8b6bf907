"""A cell on several chips serves on a mesh of exactly its chips, as its
configuration's `program.mesh` gives it; a one-chip cell installs none.

The run needs four devices, which the CPU backend gives only when told
before it starts, so it runs in a child process on eight forced host
devices, sharded (`pallas_sharded_interpret`, every quantized site and
the paged KV cache split over the model axis)."""
import dataclasses
import json
import os
import subprocess
import sys
import time

from perfbench.lib.cell import ROOT
from perfbench.tests import tiny


def _serve_both(cache_dir: str) -> dict:
    """The toy cell on four chips over a 1x4 mesh, then on one chip, in
    this process; what each saw."""
    from pathlib import Path

    from perfbench.lib import bench
    from repro.backends import sharded
    bench.CACHE_DIR = Path(cache_dir)
    seen = {}

    def look(name):
        def at_build(eng):
            mesh = sharded.current_mesh()
            seen[name] = None if mesh is None else dict(mesh.shape)
        return at_build

    # answers of 16-24 tokens, so that requests finish in a short window
    # under the sharded interpreter, which takes several times the `xla`
    # backend's step
    short = dict(tiny.TRAFFIC, output_len={"dist": "uniform", "min": 16,
                                           "max": 24})
    cfg = dataclasses.replace(tiny.CONFIG, num_key_value_heads=4,
                              backend="pallas_sharded_interpret",
                              mesh=(1, 4))
    four = dataclasses.replace(tiny.cell(), name="tiny.mesh", chips=4,
                               config=cfg, traffic=short)
    one = dataclasses.replace(tiny.cell(), traffic=short,
                              config=dataclasses.replace(tiny.CONFIG,
                                                         mesh=(1, 4)))
    out = {}
    for name, cell in (("four", four), ("one", one)):
        r = bench.run(cell, 2 ** 34 + 3, 6.0, False, time.monotonic(),
                      require_tpu=False, fault=look(name))
        out[name] = {"mesh": seen[name], "correct": r["correct"],
                     "attempted": r["attempted"], "failed": r["failed"],
                     "devices": r["device"]["count"],
                     "checks": {k: c["value"]
                                for k, c in r["checks"].items()},
                     "mesh_after": sharded.current_mesh() is not None}
    return out


def test_a_four_chip_cell_serves_on_its_mesh(tmp_path):
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags += " --xla_force_host_platform_device_count=8"
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags.strip(),
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    code = ("import json, sys; from perfbench.tests.test_perfbench_mesh "
            "import _serve_both; print(json.dumps(_serve_both(sys.argv[1])))")
    p = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    four, one = out["four"], out["one"]
    assert four["mesh"] == {"data": 1, "model": 4}
    assert four["devices"] == 4
    assert four["attempted"] > 0 and four["failed"] == 0
    # served on the mesh with no decline and checked against the
    # reference; `window_compiles` is left out here: on a mesh the steps
    # compile again once fed their own sharded outputs, and with answers
    # this short one such compile can fall in the window
    assert four["checks"]["declines"] == 0
    assert four["checks"]["median_rank"] <= tiny.LIMIT
    assert not four["mesh_after"]
    # a one-chip cell passes no mesh, whatever its file says
    assert one["mesh"] is None and one["devices"] == 1
    assert one["attempted"] > 0 and one["correct"], one["checks"]
