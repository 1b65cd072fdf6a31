"""Unit tests for the static-analysis toolbox: the HLO collective
parsers, the AST lint rules, and the BlockSpec VMEM estimators.
Everything here is jax-free except the estimators' padding import — no
tracing, no devices."""
import json

from repro.analysis import hlo as hlo_mod

# ------------------------------------------------------------- fixtures

COLLECTIVE_HLO = """\
  %ag = f32[16,128]{1,0} all-gather(f32[2,128]{1,0} %x), dimensions={0}
  %ar = f32[64]{0} all-reduce(f32[64]{0} %y), to_apply=%sum
"""

STABLEHLO = """\
  func.func public @main(%arg0: tensor<8xf32> {tf.aliasing_output = 0 : i32}, %arg1: tensor<8xf32> {tf.aliasing_output = 1 : i32}) -> tensor<8xf32> {
    %0 = "stablehlo.all_gather"(%arg0) : (tensor<8xf32>) -> tensor<64xf32>
    %1 = "stablehlo.reduce_scatter"(%0) : (tensor<64xf32>) -> tensor<8xf32>
"""


def test_parse_hlo_collectives_bytes():
    got = hlo_mod.parse_hlo_collectives(COLLECTIVE_HLO)
    assert got["all-gather"] == {"count": 1, "bytes": 16 * 128 * 4}
    # all-reduce counts both phases: 2 × 64 × 4
    assert got["all-reduce"] == {"count": 1, "bytes": 2 * 64 * 4}
    assert hlo_mod.collective_bytes(COLLECTIVE_HLO) == 8192 + 512


def test_stablehlo_counters():
    got = hlo_mod.count_stablehlo_collectives(STABLEHLO)
    assert got == {"all-gather": 1, "reduce-scatter": 1}
    assert hlo_mod.count_aliased_args(STABLEHLO) == 2


# ------------------------------------------------------------- lint rules

def _lint(src):
    from repro.analysis.lint import lint_source
    return lint_source(src, "mod.py")


def _rules(src):
    return [f.rule for f in _lint(src)]


def test_lint_call_time_jit_in_body():
    src = ("import jax\n"
           "def f(x):\n"
           "    g = jax.jit(lambda y: y + 1)\n"
           "    return g(x)\n")
    (f,) = _lint(src)
    assert f.rule == "call-time-jit" and f.symbol == "f" and f.line == 3


def test_lint_call_time_jit_decorator_form():
    src = ("import jax\n"
           "def outer(n):\n"
           "    @jax.jit\n"
           "    def inner(x):\n"
           "        return x * n\n"
           "    return inner\n")
    assert "call-time-jit" in _rules(src)


def test_lint_cached_factory_exempt():
    src = ("import functools, jax\n"
           "@functools.lru_cache(maxsize=8)\n"
           "def make(n):\n"
           "    @jax.jit\n"
           "    def inner(x):\n"
           "        return x * n\n"
           "    return inner\n")
    assert _lint(src) == []


def test_lint_module_level_jit_ok():
    assert _lint("import jax\nstep = jax.jit(lambda x: x + 1)\n") == []


def test_lint_unbounded_cache():
    src = ("import functools\n"
           "@functools.lru_cache(maxsize=None)\n"
           "def a(k):\n"
           "    return k\n"
           "@functools.cache\n"
           "def b(k):\n"
           "    return k\n"
           "@functools.lru_cache(maxsize=32)\n"
           "def c(k):\n"
           "    return k\n")
    assert _rules(src) == ["unbounded-cache", "unbounded-cache"]


def test_lint_host_sync_only_in_traced():
    traced = ("import jax\n"
              "@jax.jit\n"
              "def step(x):\n"
              "    return float(x) + 1.0\n")
    assert _rules(traced) == ["host-sync"]
    untraced = ("def report(x):\n"
                "    return float(x) + 1.0\n")
    assert _lint(untraced) == []


def test_lint_host_sync_propagates_to_callee():
    src = ("import jax\n"
           "def helper(x):\n"
           "    return x.item()\n"
           "@jax.jit\n"
           "def step(x):\n"
           "    return helper(x)\n")
    assert "host-sync" in _rules(src)


def test_lint_bitwise_reassoc():
    over_list = "import jax.numpy as jnp\nz = jnp.sum([a, b, c])\n"
    assert _rules(over_list) == ["bitwise-reassoc"]
    contract = ("import jax.numpy as jnp\n"
                "def fold(xs):\n"
                "    \"\"\"Bitwise-identical partial sums.\"\"\"\n"
                "    return jnp.sum(xs)\n")
    assert _rules(contract) == ["bitwise-reassoc"]
    plain = ("import jax.numpy as jnp\n"
             "def fold(xs):\n"
             "    return jnp.sum(xs)\n")
    assert _lint(plain) == []


def test_lint_inline_suppression():
    src = ("import jax\n"
           "def f(x):\n"
           "    # lint-ok: call-time-jit (test)\n"
           "    g = jax.jit(lambda y: y + 1)\n"
           "    return g(x)\n")
    assert _lint(src) == []
    wrong_rule = src.replace("call-time-jit (test)", "host-sync (test)")
    assert _rules(wrong_rule) == ["call-time-jit"]


def _kwonly_fn(name, n, extra=""):
    kws = ", ".join(f"k{i}=0" for i in range(n))
    return f"def {name}(x, *, {kws}{extra}):\n    return x\n"


def test_lint_config_sprawl_fires_over_threshold():
    assert _rules(_kwonly_fn("run", 9)) == ["config-sprawl"]
    assert _lint(_kwonly_fn("run", 8)) == []          # at the limit: ok


def test_lint_config_sprawl_options_param_exempt():
    assert _lint(_kwonly_fn("run", 9, ", options=None")) == []
    assert _lint(_kwonly_fn("run", 9, ", align=None")) == []


def test_lint_config_sprawl_private_and_nested_exempt():
    assert _lint(_kwonly_fn("_run", 9)) == []
    nested = "def outer():\n" + "    " + \
        _kwonly_fn("inner", 9).replace("\n    ", "\n        ")
    assert _lint(nested) == []


def test_lint_config_sprawl_inline_suppression():
    src = "# lint-ok: config-sprawl (test)\n" + _kwonly_fn("run", 9)
    assert _lint(src) == []


def test_lint_baseline_matching(tmp_path):
    from repro.analysis.lint import (lint_source, load_baseline,
                                     split_baselined)
    src = ("import jax\n"
           "def f(x):\n"
           "    g = jax.jit(lambda y: y + 1)\n"
           "    return g(x)\n")
    findings = lint_source(src, "src/repro/mod.py")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(
        [{"rule": "call-time-jit", "path": "repro/mod.py",
          "symbol": "f"}]))
    new, accepted = split_baselined(findings, load_baseline(bl))
    assert new == [] and len(accepted) == 1
    # a different symbol does not match
    new, accepted = split_baselined(
        findings, [{"rule": "call-time-jit", "path": "repro/mod.py",
                    "symbol": "g"}])
    assert len(new) == 1 and accepted == []


def test_lint_syntax_error_is_a_finding():
    assert _rules("def f(:\n") == ["syntax-error"]


# -------------------------------------------------------- vmem estimates


def test_blocks_dense_fits():
    from repro.analysis.blocks import splitnn_bottom_blocks
    r = splitnn_bottom_blocks(512, 128, 128)
    assert r.resident_bytes == 4 * (512 * 128 + 128 * 128 + 128
                                    + 512 * 128)
    assert r.ok and not r.fallback


def test_blocks_gather_fallback_boundary():
    from repro.analysis.blocks import splitnn_bottom_gather_blocks
    from repro.kernels.padding import GATHER_VMEM_BUDGET
    rows = GATHER_VMEM_BUDGET // (4 * 128)     # N at d_pad=128
    at = splitnn_bottom_gather_blocks(rows, 128, 128, 512)
    over = splitnn_bottom_gather_blocks(rows + 1, 128, 128, 512)
    assert not at.fallback and at.ok           # exactly at budget: launches
    assert over.fallback and over.ok           # past it: wrapper falls back


def test_blocks_sorted_intersect_regimes():
    from repro.analysis.blocks import VMEM_BUDGET, sorted_intersect_blocks
    from repro.kernels.sorted_intersect.kernel import SINGLE_PASS_MAX_P
    # admission boundary: the largest admitted single-pass P fills the
    # double-buffered 64 B/element blocks exactly to 16 MB; the tiled
    # local chunk spans the same P, one element more routes tiled
    assert 64 * SINGLE_PASS_MAX_P == VMEM_BUDGET
    at = sorted_intersect_blocks(SINGLE_PASS_MAX_P)
    assert at.ok and not at.note
    assert at.resident_bytes == 64 * SINGLE_PASS_MAX_P == at.budget
    over = sorted_intersect_blocks(SINGLE_PASS_MAX_P + 1)
    assert over.ok and "tiled" in over.note
    # 2^19, which the v5e compiler refuses in one pass, routes tiled
    first = sorted_intersect_blocks(1 << 19)
    assert first.ok and "tiled" in first.note
    tiled = sorted_intersect_blocks(1 << 21)
    assert tiled.ok and "tiled" in tiled.note
    assert tiled.resident_bytes == 2 * 4 * 4 * (2 * SINGLE_PASS_MAX_P)


def test_blocks_default_matrix_all_ok():
    from repro.analysis.blocks import vmem_report
    rows = [r.as_row() for r in vmem_report()]
    assert len(rows) >= 8
    assert all(r["ok"] for r in rows)
