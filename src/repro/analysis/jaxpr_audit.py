"""Declarative contract audit over the engine's traced step programs.

``GenerationEngine.step_program(which)`` exposes every device program the
serving loop can dispatch — fused ragged/padded mixed-batch steps, the
Pallas and gather-oracle decode programs, and the bare pool
gather/scatter roundtrip. This module traces each one and checks a
:class:`StepContract` against it:

* **collective census** — two-level: the *jaxpr* census counts explicit
  collectives (shard_map psums carry their mesh axis name, so violations
  name the axis), while the *HLO* census (models.shardmap_tp
  .count_collectives) additionally sees partitioner-inserted collectives
  that never appear in the jaxpr (e.g. the data-axis all-reduce GSPMD
  adds to combine masked block-gathers under ``dp_blocks``). Every step
  program must be all-gather/all-to-all/reduce-scatter-free: the
  gather/scatter over host-resident block tables must never communicate.
* **int8 dtype flow** — on quantized engines with the Pallas kernels,
  the int8 pool operands must reach a ``pallas_call`` still int8 (dequant
  fused in-kernel); a whole-pool ``convert_element_type`` to float means
  XLA is materializing a dequantized copy of the entire pool per step.
  Gathered-slice converts (the requant path, the gather oracle) are
  legal and not flagged.
* **callback scan** — no host callbacks (``pure_callback``,
  ``io_callback``, ``debug_callback``) or infeed/outfeed inside any step
  program: a hidden host round-trip per step destroys dispatch overlap.
* **compile-cache sentinel** — after ``warmup_step_variants()`` the
  ragged step's jit cache must hold exactly the warmed pack-aligned
  buckets; growth past that means some dispatch path is minting
  off-bucket packed lengths (a silent mid-serve compile).

Run via ``audit_engine(engine)``, the ``python -m repro.analysis jaxpr``
CLI, or ``launch/serve.py --audit``. Each check is mutation-tested in
tests/test_analysis.py (see the CLI's ``--mutate`` registry)."""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.extend import core as jcore

__all__ = [
    "StepContract", "Finding", "AuditReport", "audit_engine",
    "audit_program", "default_contracts", "collective_census_jaxpr",
    "find_callbacks", "int8_kernel_flow", "cache_sentinel", "iter_eqns",
]

# jaxpr primitive -> census kind (names normalized: psum2 -> psum etc.)
_COLLECTIVE_KINDS = {
    "psum": "all-reduce",
    "pmax": "all-reduce",
    "pmin": "all-reduce",
    "all_gather": "all-gather",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
    "pshuffle": "collective-permute",
    "psum_scatter": "reduce-scatter",
    "reduce_scatter": "reduce-scatter",
}

_CALLBACK_MARKERS = ("callback", "infeed", "outfeed")


@dataclass(frozen=True)
class StepContract:
    """Declarative expectations for one traced step program."""
    program: str                       # step_program() target name
    max_all_gather: int = 0            # HLO census bound (0 on every path)
    max_all_reduce: Optional[int] = None   # None = unbounded (TP matmuls)
    forbid_kinds: Tuple[str, ...] = ("all-to-all", "reduce-scatter")
    allow_callbacks: bool = False
    require_int8_kernel_path: bool = False


@dataclass(frozen=True)
class Finding:
    program: str
    check: str      # collectives / callbacks / int8-flow / cache-sentinel
    ok: bool
    detail: str

    def __str__(self) -> str:
        mark = " ok " if self.ok else "FAIL"
        return f"[{mark}] {self.program:>13s} {self.check:<13s} {self.detail}"


@dataclass
class AuditReport:
    findings: List[Finding] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return all(f.ok for f in self.findings)

    def failures(self) -> List[Finding]:
        return [f for f in self.findings if not f.ok]

    def render(self) -> str:
        head = "step-program contract audit"
        tail = ("all contracts hold" if self.ok
                else f"{len(self.failures())} contract violation(s)")
        return "\n".join([head, *(str(f) for f in self.findings), tail])


# ------------------------------------------------------------ jaxpr walking
def _sub_jaxprs(eqn) -> List[Any]:
    """Inner jaxprs of a control-flow/call eqn (pjit, scan, while, cond,
    custom_jvp...). pallas_call is deliberately excluded — its body is the
    kernel, a different machine; the eqn itself marks the boundary."""
    if eqn.primitive.name == "pallas_call":
        return []
    subs: List[Any] = []
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jcore.ClosedJaxpr):
                subs.append(v.jaxpr)
            elif isinstance(v, jcore.Jaxpr):
                subs.append(v)
    return subs


def iter_eqns(jaxpr) -> Iterator[Any]:
    """All eqns of a (Closed)Jaxpr, recursing through call/control-flow
    sub-jaxprs (not into pallas kernel bodies)."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def trace_step(jitted, args) -> Any:
    """ClosedJaxpr of a (jitted) step program against its example args."""
    return jax.make_jaxpr(jitted)(*args)


# ------------------------------------------------------- collective census
def collective_census_jaxpr(closed) -> Dict[str, Dict[str, int]]:
    """Per-mesh-axis census of EXPLICIT collectives in the traced program
    (shard_map bodies carry axis names). Partitioner-inserted collectives
    don't exist at this level — pair with the HLO census for totals."""
    out: Dict[str, Dict[str, int]] = {}
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name.rstrip("0123456789")
        kind = _COLLECTIVE_KINDS.get(name)
        if kind is None:
            continue
        axes = eqn.params.get("axes", eqn.params.get("axis_name", ("?",)))
        if not isinstance(axes, (tuple, list)):
            axes = (axes,)
        for ax in axes:
            per = out.setdefault(str(ax), {})
            per[kind] = per.get(kind, 0) + 1
    return out


# ----------------------------------------------------------- callback scan
def find_callbacks(closed) -> List[str]:
    """Host-callback / infeed primitives anywhere in the step program."""
    hits = []
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        if any(m in name for m in _CALLBACK_MARKERS):
            hits.append(name)
    return hits


# ---------------------------------------------------------- int8 dtype flow
def _is_var(v) -> bool:
    return isinstance(v, jcore.Var)


# ops through which a full-pool value stays THE pool (content-complete):
# in-place scatters, layout changes. A gather/slice demotes to DERIVED —
# converting gathered slices to float (requant, oracle dequant) is legal.
_POOL_ALIAS_PRIMS = ("reshape", "transpose", "squeeze", "expand_dims",
                     "scatter", "copy")


def int8_kernel_flow(closed) -> Tuple[bool, List[str]]:
    """Two-level taint walk of the int8 pool operands.

    Seeds (the int8 pool invars, ndim >= 4) start at level ``POOL`` — "this
    value IS the whole pool". POOL survives only content-complete ops
    (reshape/transpose/scatter); any gather or slice demotes the result to
    ``DERIVED``. Returns ``(reached_kernel, upcasts)``: whether some
    ``pallas_call`` consumes a still-int8 tainted operand, and every
    int8 -> float ``convert_element_type`` applied at POOL level — i.e. XLA
    materializing a dequantized copy of the entire pool, which the fused
    in-kernel dequant exists to avoid. DERIVED converts (the running-scale
    requant of affected blocks, the gather oracle) are not flagged."""
    jaxpr = closed.jaxpr if isinstance(closed, jcore.ClosedJaxpr) else closed
    int8 = jnp.dtype("int8")
    seeds = [v for v in jaxpr.invars
             if getattr(v.aval, "dtype", None) == int8
             and getattr(v.aval, "ndim", 0) >= 4]
    if not seeds:
        return False, []
    report_reached: List[bool] = []
    upcasts: List[str] = []

    def flow(jx, tainted: Dict[Any, str]) -> Dict[Any, str]:
        for eqn in jx.eqns:
            t_in = [v for v in eqn.invars if _is_var(v) and v in tainted]
            name = eqn.primitive.name
            if name == "pallas_call":
                if any(v.aval.dtype == int8 for v in t_in):
                    report_reached.append(True)
                continue
            if name == "convert_element_type" and t_in:
                src = eqn.invars[0]
                new = eqn.params.get("new_dtype")
                if (_is_var(src) and tainted.get(src) == "POOL"
                        and src.aval.dtype == int8
                        and new is not None
                        and jnp.issubdtype(new, jnp.floating)):
                    upcasts.append(
                        f"convert_element_type int8{list(src.aval.shape)}"
                        f" -> {jnp.dtype(new).name} "
                        f"(whole-pool dequant outside the kernel)")
            subs = _sub_jaxprs(eqn)
            for sub in subs:
                # align operands to binder vars from the END: calls map
                # positionally, cond carries a leading predicate operand
                sub_tainted: Dict[Any, str] = {}
                for ev, sv in zip(reversed(eqn.invars), reversed(sub.invars)):
                    if _is_var(ev) and ev in tainted:
                        sub_tainted[sv] = tainted[ev]
                inner = flow(sub, sub_tainted)
                for eo, so in zip(reversed(eqn.outvars),
                                  reversed(sub.outvars)):
                    if (_is_var(so) and so in inner and _is_var(eo)
                            and getattr(eo.aval, "dtype", None) == int8):
                        tainted[eo] = inner[so]
            if not subs and t_in:
                level = ("POOL" if name.startswith(_POOL_ALIAS_PRIMS)
                         and any(tainted[v] == "POOL" for v in t_in)
                         else "DERIVED")
                for o in eqn.outvars:
                    if _is_var(o) and getattr(o.aval, "dtype", None) == int8:
                        tainted[o] = level
        return tainted

    flow(jaxpr, {v: "POOL" for v in seeds})
    return bool(report_reached), upcasts


# -------------------------------------------------------- cache sentinel
def cache_sentinel(engine, warm: bool = True) -> Finding:
    """Compile-cache sentinel: after warmup, the ragged step jit must hold
    exactly the warmed pack-aligned bucket variants — growth means some
    path is minting off-bucket packed lengths (silent mid-serve compiles)."""
    if engine.backend != "paged" or not engine.interleave or not engine.ragged:
        return Finding("fused_ragged", "cache-sentinel", True,
                       "n/a (no ragged variants on this engine)")
    buckets = engine.warmup_step_variants() if warm else None
    size_of = getattr(engine._ragged_step_jit, "_cache_size", None)
    if size_of is None:  # jax without cache introspection
        return Finding("fused_ragged", "cache-sentinel", True,
                       "n/a (jit cache size not introspectable)")
    size = size_of()
    if buckets is None:
        return Finding("fused_ragged", "cache-sentinel", True,
                       f"{size} cached variant(s) (no warmup baseline)")
    ok = size <= buckets
    return Finding(
        "fused_ragged", "cache-sentinel", ok,
        f"{size} cached variant(s) vs {buckets} warmed bucket(s)"
        + ("" if ok else " — off-bucket packed length compiled"))


# ----------------------------------------------------------- program audit
def audit_program(engine, contract: StepContract) -> List[Finding]:
    """Trace one step program and check its contract; returns findings for
    the collective census, callback scan, and (if required) int8 flow."""
    from repro.models.shardmap_tp import count_collectives

    jitted, args = engine.step_program(contract.program)
    closed = trace_step(jitted, args)
    findings: List[Finding] = []

    # collectives, censused at both levels: HLO sees partitioner-inserted
    # ops the jaxpr can't; the jaxpr sees explicit collectives a 1-device
    # compile would fold away (and names their mesh axis). The contract
    # bounds the worse of the two.
    hlo = count_collectives(jitted.lower(*args).compile())
    per_axis = collective_census_jaxpr(closed)
    jx_total: Dict[str, int] = {}
    for kinds in per_axis.values():
        for kind, n in kinds.items():
            jx_total[kind] = jx_total.get(kind, 0) + n
    eff = {k: max(hlo.get(k, 0), jx_total.get(k, 0))
           for k in set(hlo) | set(jx_total)}
    problems = []
    if eff.get("all-gather", 0) > contract.max_all_gather:
        problems.append(f"all-gather={eff['all-gather']}"
                        f" > {contract.max_all_gather}")
    for kind in contract.forbid_kinds:
        if eff.get(kind, 0):
            problems.append(f"{kind}={eff[kind]} (forbidden)")
    if (contract.max_all_reduce is not None
            and eff.get("all-reduce", 0) > contract.max_all_reduce):
        problems.append(f"all-reduce={eff['all-reduce']}"
                        f" > {contract.max_all_reduce}")
    axis_note = ("; explicit by axis: " + ", ".join(
        f"{ax}:{kind}={n}" for ax, kinds in sorted(per_axis.items())
        for kind, n in sorted(kinds.items()))
        if per_axis else "")
    findings.append(Finding(
        contract.program, "collectives", not problems,
        ("; ".join(problems) if problems else
         " ".join(f"{k}={v}" for k, v in sorted(eff.items()) if v) or
         "collective-free") + axis_note))

    # host callbacks
    cbs = find_callbacks(closed)
    findings.append(Finding(
        contract.program, "callbacks", contract.allow_callbacks or not cbs,
        ("none" if not cbs else
         f"host round-trip inside step: {', '.join(sorted(set(cbs)))}")))

    # int8 pool dtype flow
    if contract.require_int8_kernel_path:
        reached, upcasts = int8_kernel_flow(closed)
        ok = reached and not upcasts
        if ok:
            detail = "int8 pools reach pallas_call un-upcast"
        elif not reached:
            detail = ("no pallas_call consumes the int8 pools "
                      "(dequant happens in XLA, not in-kernel)")
        else:
            detail = "; ".join(upcasts)
        findings.append(Finding(contract.program, "int8-flow", ok, detail))
    return findings


def default_contracts(engine) -> List[StepContract]:
    """The engine's standing contracts, derived from its configuration:
    every program is all-gather-free; off-mesh engines are collective-free
    entirely; int8 + pallas engines must dequantize in-kernel on the
    kernelized programs (ragged fused step, pallas decode)."""
    on_mesh = engine.pool_layout is not None
    ar = None if on_mesh else 0
    int8k = engine.kv_dtype == "int8" and engine.kernel == "pallas"
    fused = "fused_ragged" if engine.ragged else "fused_padded"
    contracts = [
        StepContract(fused, max_all_reduce=ar,
                     require_int8_kernel_path=int8k),
        StepContract("decode", max_all_reduce=ar,
                     require_int8_kernel_path=int8k),
        StepContract("decode_ref", max_all_reduce=ar),
        StepContract("pool", max_all_reduce=1 if on_mesh else 0),
    ]
    return contracts


def audit_engine(engine, contracts: Optional[Sequence[StepContract]] = None,
                 warm: bool = True) -> AuditReport:
    """Audit every (or the given) step-program contract plus the compile-
    cache sentinel. ``warm=True`` runs warmup_step_variants() first so the
    sentinel has its bucket baseline."""
    report = AuditReport()
    for c in (default_contracts(engine) if contracts is None else contracts):
        report.findings.extend(audit_program(engine, c))
    report.findings.append(cache_sentinel(engine, warm=warm))
    return report
