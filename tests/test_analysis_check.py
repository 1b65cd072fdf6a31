"""End-to-end tests for the static engine-contract gate
(``python -m repro.analysis.check``): exit codes against the real repo,
a planted lint violation, a doctored contract; plus in-process census
invariants (ONE all_gather per step across mesh shapes, including the
``4x2`` shape the dynamic CI contract never runs) and the bounded
program-cache behavior the gate's lint rules exist to protect."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.census import ProgramCensus, census_program
from repro.core.splitnn import SplitNNConfig

REPO = Path(__file__).resolve().parents[1]

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs >=8 devices for the mesh census matrix "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def run_check(*args):
    env = {**os.environ, "PYTHONPATH": str(REPO / "src")}
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.check", *args],
        cwd=REPO, env=env, capture_output=True, text=True)


# ------------------------------------------------------------ exit codes


def test_check_passes_on_repo():
    r = run_check()
    assert r.returncode == 0, r.stdout + r.stderr
    assert "static contract OK" in r.stdout


def test_check_fails_on_planted_lint_violations(tmp_path):
    (tmp_path / "bad.py").write_text(
        "import functools\n"
        "import jax\n"
        "@functools.lru_cache(maxsize=None)\n"
        "def leaky(mesh):\n"
        "    return mesh\n"
        "def f(x):\n"
        "    g = jax.jit(lambda y: y + 1)\n"
        "    return g(x)\n")
    r = run_check("--lint-only", "--src", str(tmp_path),
                  "--baseline", str(tmp_path / "empty_baseline.json"))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "unbounded-cache" in r.stdout
    assert "call-time-jit" in r.stdout


def test_check_fails_on_doctored_contract(tmp_path):
    doc = json.loads(
        (REPO / "experiments/bench/static_contract.json").read_text())
    row = next(r for r in doc["rows"]
               if r["engine"] == "kmeans.fit+ref" and r["mesh"] == "1")
    row["counters"]["all_gather"] = 3          # the engine has none
    doctored = tmp_path / "doctored.json"
    doctored.write_text(json.dumps(doc))
    r = run_check("--census-only", "--contract", str(doctored))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "contract pins 3" in r.stdout


def test_check_fails_on_missing_contract_and_does_not_write(tmp_path):
    missing = tmp_path / "nope.json"
    r = run_check("--census-only", "--contract", str(missing))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "generate it with --write" in r.stdout
    assert not missing.exists()


def test_write_refuses_while_lint_fails(tmp_path):
    """--write must not regenerate the contract over a dirty tree."""
    (tmp_path / "bad.py").write_text(
        "import jax\n"
        "def f(x):\n"
        "    return jax.jit(lambda y: y)(x)\n")
    target = tmp_path / "contract.json"
    r = run_check("--write", "--contract", str(target),
                  "--src", str(tmp_path),
                  "--baseline", str(tmp_path / "empty_baseline.json"))
    assert r.returncode == 1, r.stdout + r.stderr
    assert not target.exists()


# ------------------------------------------------- census unit behavior


def test_census_counts_callbacks_and_f64():
    def fn(x):
        y = jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct((), jnp.float32), x)
        return y.astype(jnp.float64) + 1.0

    with jax.enable_x64(True):
        c = census_program(
            fn, (jax.ShapeDtypeStruct((), jnp.float32),),
            count_donation=False)
    assert c.callbacks == 1
    assert c.f64_widenings >= 1
    assert c.f64_values >= 1


def test_census_collective_inside_scan():
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))

    def inner(xs):
        def body(c, x):
            return c + jax.lax.psum(x, "d"), x
        out, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        return out

    fn = shard_map(inner, mesh=mesh, in_specs=P("d"), out_specs=P(),
                   check_rep=False)
    c = census_program(fn, (jax.ShapeDtypeStruct((8,), jnp.float32),),
                       count_donation=False)
    assert c.collectives == {"psum": 1}
    assert c.collectives_in_loop == {"psum": 1}
    assert c.scan_lengths == [8]


def test_census_counts_donated_args():
    fn = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
    sds = jax.ShapeDtypeStruct((4,), jnp.float32)
    c = census_program(fn, (sds, sds))
    assert c.donated_args == 1


def test_write_census_csv_roundtrip(tmp_path):
    from repro.analysis.check import write_census_csv

    c = ProgramCensus()
    c.scan_lengths = [5, 3]
    path = tmp_path / "census.csv"
    write_census_csv({("train.epoch.lr+ref", "2x4"): c.counters()},
                     str(path))
    header, line = path.read_text().strip().split("\n")
    assert header.startswith("engine,mesh,all_gather,")
    assert line.startswith("train.epoch.lr+ref,2x4,")
    assert "3;5" in line                        # list fields join with ;


# ----------------------------------------- the ONE-all-gather invariant


@needs_8_devices
@pytest.mark.parametrize("mesh_name,want_ag", [
    ("8", 0),        # 1-D data mesh: no model axis, no gathers
    ("2x4", 1),      # the CI mesh
    ("4x2", 1),      # a shape the dynamic contract never runs
])
def test_epoch_program_one_all_gather_per_step(mesh_name, want_ag):
    from repro.launch.mesh import make_data_mesh, make_train_mesh
    from repro.sharding import resolve_train_mesh
    from repro.train.vfl import make_epoch_fn

    raw = (make_data_mesh(8) if mesh_name == "8"
           else make_train_mesh(*(int(x) for x in mesh_name.split("x"))))
    mesh, data_axis, n_data, model_axis, n_model = resolve_train_mesh(raw)
    cfg = SplitNNConfig("lr", 2, batch_size=64)
    prog = make_epoch_fn(cfg, (3, 4, 5), mesh, data_axis, model_axis,
                         n_data, n_model, "ref", 512, True)
    c = census_program(prog.jitted, prog.abstract_args(n=256, bs=64))
    assert c.collectives_in_loop.get("all_gather", 0) == want_ag
    assert c.callbacks == 0
    assert c.f64_values == 0


# ------------------------------------------------- bounded program caches


def test_epoch_program_cache_bounded_and_clearable():
    from repro.sharding import resolve_train_mesh
    from repro.train.vfl import (_score_blocks_fn, _score_step_fn,
                                 clear_program_caches, make_epoch_fn)

    assert make_epoch_fn.cache_info().maxsize == 16
    assert _score_step_fn.cache_info().maxsize == 32
    assert _score_blocks_fn.cache_info().maxsize == 32

    mesh, data_axis, n_data, model_axis, n_model = resolve_train_mesh(None)
    cfg = SplitNNConfig("lr", 2, batch_size=64)
    args = (cfg, (3, 4, 5), mesh, data_axis, model_axis, n_data, n_model,
            "ref", 512, True)
    p1 = make_epoch_fn(*args)
    assert make_epoch_fn(*args) is p1           # cache hit
    clear_program_caches()
    assert make_epoch_fn.cache_info().currsize == 0
    assert make_epoch_fn(*args) is not p1


def test_psi_dispatch_cache_bounded_and_clearable():
    from repro.config import AlignOptions
    from repro.psi.engine import (_dispatch, clear_dispatch_cache,
                                  dispatch_key)

    assert _dispatch.cache_info().maxsize == 32
    key, _ = dispatch_key(AlignOptions(impl="ref"))
    f1 = _dispatch("prf", key)
    assert _dispatch("prf", key) is f1
    # Any AlignOptions lowering to the same executable shares the entry.
    key2, _ = dispatch_key(AlignOptions(impl="ref", protocol="oprf",
                                        overlap=0.3))
    assert _dispatch("prf", key2) is f1
    clear_dispatch_cache()
    assert _dispatch.cache_info().currsize == 0
    assert _dispatch("prf", key) is not f1
