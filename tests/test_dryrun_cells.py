"""Dry-run integration: one real cell lowered+compiled in a subprocess
(own process so the 16-device XLA flag never leaks into this test session),
plus HLO-census self-consistency checks."""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["REPRO_DRYRUN_DEVICES"] = "16"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_dryrun_single_cell_compiles_and_reports():
    out = _run(r"""
from repro.launch import dryrun
from repro.launch.mesh import make_mesh
import jax, json
mesh = make_mesh((4, 4), ("data", "model"))
res = dryrun.lower_cell("mamba2-780m", "decode_32k", mesh)
r = res["roofline"]
assert res["compile_s"] > 0
assert res["memory"]["per_device_total"] > 0
assert r["compute_s"] >= 0 and r["memory_s"] > 0
assert r["dominant"] in ("compute", "memory", "collective")
assert r["params_total"] > 5e8          # ~780M
print(json.dumps({"dom": r["dominant"],
                  "mem_gib": res["memory"]["per_device_total"] / 2**30}))
""")
    d = json.loads(out.strip().splitlines()[-1])
    assert d["mem_gib"] < 64


def test_census_matches_cost_analysis_when_unscanned():
    """With 1-layer models every while has trip 1 — census dot-flops must be
    within 2x of XLA's own (elementwise-inclusive) count."""
    out = _run(r"""
from repro.launch import dryrun
import jax, json
import dataclasses
from repro.configs import get_smoke_config
from repro.models.transformer import forward_train, param_specs
from repro.launch.hlo_census import census
import jax.numpy as jnp
cfg = dataclasses.replace(get_smoke_config("minitron-8b"), n_layers=1,
                          remat=False, q_chunk=64, kv_chunk=64,
                          loss_chunk=64)
batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32),
         "targets": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
co = jax.jit(lambda p, b: forward_train(p, cfg, b)).lower(
    param_specs(cfg), batch).compile()
cs = census(co.as_text())
raw = float((co.cost_analysis() or {}).get("flops", 0.0))
assert cs.flops > 0 and raw > 0
ratio = cs.flops / raw
assert 0.4 < ratio < 2.0, (cs.flops, raw)
print(json.dumps({"ratio": ratio}))
""")
    assert "ratio" in out


def test_all_cells_accounted():
    from repro.configs import ASSIGNED_ARCHS, SHAPES, all_cells
    cells = all_cells()
    assert len(cells) == len(ASSIGNED_ARCHS) * len(SHAPES) == 40
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(skipped) == 7            # long_500k on pure full-attention
    assert all(c[1] == "long_500k" for c in skipped)
    assert {a for a, s, ok, w in cells if s == "long_500k" and ok} == {
        "mamba2-780m", "zamba2-7b", "gemma2-9b"}


def test_sweep_artifacts_if_present():
    """Validate the committed sweep artifacts (skips if the sweep wasn't run)."""
    art = os.path.join(os.path.dirname(__file__), "..", "artifacts")
    if not os.path.isdir(art):
        pytest.skip("no artifacts/ (run repro.launch.dryrun)")
    import glob
    files = glob.glob(os.path.join(art, "*", "*.json"))
    assert files
    n_err = 0
    for f in files:
        d = json.load(open(f))
        if "error" in d:
            n_err += 1
    assert n_err == 0, f"{n_err} failed cells in artifacts"
