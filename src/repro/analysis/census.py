"""Program census: static jaxpr/StableHLO accounting for compiled engines.

The dynamic perf contract (``benchmarks/check_contract.py``) proves the
engines' dispatch/sync counters by RUNNING them on two CI mesh shapes.
This module proves the complementary *program* invariants without
executing anything: a traced program (``jax.make_jaxpr``) is walked
recursively — through ``pjit``/``scan``/``while``/``shard_map``/
``custom_vjp``/``pallas_call`` sub-jaxprs — and every occurrence of a
communication, host-boundary, or precision-hazard primitive is counted:

- collectives (``all_gather`` / ``psum`` / ``reduce_scatter`` /
  ``ppermute`` / ``all_to_all``), split into total structural
  occurrences and occurrences INSIDE loop bodies (a collective inside
  the epoch ``scan`` runs once per step, which is what the
  ONE-all-gather-per-step contract pins), plus their output bytes;
- host callbacks (``pure_callback`` / ``io_callback`` /
  ``debug_callback``) — the zero-host-sync contract of the scan engine
  means NONE may appear in any lowered engine program;
- f64 values and ``convert_element_type`` widenings to f64 — bitwise
  contract paths must stay f32/integer;
- loop trip structure: every ``scan`` length (``while`` trip counts are
  unbounded → recorded as -1);
- ``pallas_call`` sites and donated-buffer aliasing (from the lowered
  StableHLO's ``tf.aliasing_output`` annotations, see
  ``repro.analysis.hlo.count_aliased_args``).

Counts are STRUCTURAL: a collective inside a scan body counts once, with
its loop context recorded separately — per-epoch totals are
``count_in_loop × trip_count``, which the census report carries via
``scan_lengths``.  ``repro.analysis.check`` asserts these counters
against ``experiments/bench/static_contract.json`` across a matrix of
mesh shapes, including shapes the dynamic CI contract never runs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.extend.core import ClosedJaxpr
import numpy as np

# collective primitive -> canonical census name (jaxpr spelling)
COLLECTIVE_PRIMS = {
    "all_gather": "all_gather",
    "psum": "psum",
    "reduce_scatter": "reduce_scatter",  # the all_gather transpose
    "psum_scatter": "reduce_scatter",    # alias (newer jax spelling)
    "ppermute": "ppermute",
    "all_to_all": "all_to_all",
}

CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                  "callback", "outside_call", "host_callback")

COLLECTIVE_KINDS = ("all_gather", "psum", "reduce_scatter", "ppermute",
                    "all_to_all")

# the flat counter schema shared by the static contract, the census CSV
# and (via repro.analysis.check) the CI gate — one definition, like the
# dynamic contract's CONTRACT_FIELDS living on the stats dataclasses
CENSUS_FIELDS: Tuple[str, ...] = tuple(
    [f"{k}{suffix}" for k in COLLECTIVE_KINDS
     for suffix in ("", "_in_loop", "_bytes")]
    + ["callbacks", "f64_values", "f64_widenings", "pallas_calls",
       "scan_lengths", "while_loops", "donated_args"])


@dataclasses.dataclass
class ProgramCensus:
    """Structural counts for one traced program."""
    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
    collectives_in_loop: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    collective_bytes: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    callbacks: int = 0
    f64_values: int = 0
    f64_widenings: int = 0
    pallas_calls: int = 0
    scan_lengths: List[int] = dataclasses.field(default_factory=list)
    while_loops: int = 0
    donated_args: int = 0

    def counters(self) -> Dict[str, Any]:
        """The flat ``CENSUS_FIELDS`` dict the contract pins."""
        out: Dict[str, Any] = {}
        for k in COLLECTIVE_KINDS:
            out[k] = self.collectives.get(k, 0)
            out[f"{k}_in_loop"] = self.collectives_in_loop.get(k, 0)
            out[f"{k}_bytes"] = self.collective_bytes.get(k, 0)
        out["callbacks"] = self.callbacks
        out["f64_values"] = self.f64_values
        out["f64_widenings"] = self.f64_widenings
        out["pallas_calls"] = self.pallas_calls
        out["scan_lengths"] = sorted(self.scan_lengths)
        out["while_loops"] = self.while_loops
        out["donated_args"] = self.donated_args
        return out

    def total_collectives(self) -> int:
        return sum(self.collectives.values())


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64)
                   * np.dtype(aval.dtype).itemsize) if aval.shape else \
            int(np.dtype(aval.dtype).itemsize)
    except Exception:
        return 0


def _is_f64(aval) -> bool:
    try:
        return np.dtype(aval.dtype) == np.float64
    except Exception:
        return False


def _sub_jaxprs(params: Dict[str, Any]):
    """Yield every jaxpr-valued equation param (covers ``pjit``'s
    ClosedJaxpr, ``shard_map``'s bare Jaxpr, scan/while bodies,
    custom_vjp branch tuples, pallas_call kernel jaxprs, ...)."""
    for val in params.values():
        items = val if isinstance(val, (tuple, list)) else (val,)
        for item in items:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif hasattr(item, "eqns"):
                yield item


def census_jaxpr(closed_jaxpr, *, donated_args: int = 0) -> ProgramCensus:
    """Walk a (closed) jaxpr recursively and count the census primitives.

    ``scan``/``while`` sub-jaxprs are walked with the loop flag set, so
    collectives inside them land in ``collectives_in_loop`` as well as
    the structural totals.
    """
    c = ProgramCensus(donated_args=donated_args)
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

    def walk(jx, in_loop: bool) -> None:
        for eqn in jx.eqns:
            name = eqn.primitive.name
            kind = COLLECTIVE_PRIMS.get(name)
            if kind is not None:
                c.collectives[kind] = c.collectives.get(kind, 0) + 1
                if in_loop:
                    c.collectives_in_loop[kind] = \
                        c.collectives_in_loop.get(kind, 0) + 1
                b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
                c.collective_bytes[kind] = \
                    c.collective_bytes.get(kind, 0) + b
            if name in CALLBACK_PRIMS:
                c.callbacks += 1
            if name == "pallas_call":
                c.pallas_calls += 1
            if name == "convert_element_type" and _is_f64(
                    eqn.outvars[0].aval):
                c.f64_widenings += 1
            for v in eqn.outvars:
                if _is_f64(v.aval):
                    c.f64_values += 1
            child_in_loop = in_loop
            if name == "scan":
                c.scan_lengths.append(int(eqn.params.get("length", -1)))
                child_in_loop = True
            elif name == "while":
                c.while_loops += 1
                c.scan_lengths.append(-1)
                child_in_loop = True
            for sub in _sub_jaxprs(eqn.params):
                walk(sub, child_in_loop)

    walk(jaxpr, False)
    return c


def census_program(fn, args: Sequence[Any], *,
                   count_donation: bool = True) -> ProgramCensus:
    """Trace ``fn(*args)`` (never execute it) and census the jaxpr.

    ``args`` may be ``jax.ShapeDtypeStruct``s — the program is built
    abstractly, exactly as ``jax.jit(fn).lower`` would build it.
    Donated-buffer aliasing is read from the lowered StableHLO text
    (the only place jit-level donation is visible) when ``fn`` is a
    jit-wrapped callable; tracing failures there degrade to 0 rather
    than failing the census.
    """
    jx = jax.make_jaxpr(fn)(*args)
    donated = 0
    if count_donation:
        try:
            from repro.analysis.hlo import count_aliased_args
            # lint-ok: call-time-jit (lower-only wrapper, never executed)
            lowered = jax.jit(fn).lower(*args) if not hasattr(fn, "lower") \
                else fn.lower(*args)
            donated = count_aliased_args(lowered.as_text())
        except Exception:
            donated = 0
    return census_jaxpr(jx, donated_args=donated)
