"""Jaxpr walker: liveness peak-memory estimate + bf16→f32 promotion audit.

``audit_program`` traces one registry entry with ``jax.make_jaxpr`` and
walks the resulting IR:

- **liveness** — a linear scan over the equations: each output buffer is
  born at its equation and dies after its last use (program outputs live
  to the end), so the running live-set total is a peak-memory estimate
  with *per-buffer provenance* — which primitive and which source line
  created each buffer (``source_info_util.user_frame``).  Call-like
  primitives (``pjit``/``scan``/``cond``/``while``/``pallas_call``/custom
  VJPs) are handled by recursion: an inner jaxpr contributes its own peak
  minus its input bytes (those are views of outer buffers) as transient
  overhead at the call site.  This is an estimate of what the program
  *asks for*, not what XLA schedules after fusion — it upper-bounds real
  allocation and, crucially for the scaling gate, it scales in K exactly
  like the real thing.
- **dtype promotion** — inside a ``compute_dtype="bf16"`` program, an
  f32 tensor born from bf16 operands is a silent upcast (the PR-7
  regression class: one stray promotion drags the whole epoch back to
  f32).  jnp implements implicit promotion *via*
  ``convert_element_type``, so the audit flags bf16→f32 converts whose
  source line shows no cast of its own (a visible ``astype``/``float32``
  on the line is deliberate and owned by the AST ``dtype-thread`` rule)
  plus any other primitive minting f32 straight from bf16 operands — a
  ``dot_general``/``conv`` with an explicit f32
  ``preferred_element_type`` excepted (the documented accumulator
  idiom).

Findings carry real ``path:line`` sites, so the CLI's pragma + baseline
machinery applies to them unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax._src import core as jcore
from jax._src import source_info_util as _siu

from repro.analysis.findings import Finding
from repro.analysis.ir.programs import EngineProgram

TOP_N = 8            # live buffers reported at the peak program point

# f32-accumulating contractions are policy, not leaks
_ACCUM_PRIMS = frozenset({"dot_general", "conv_general_dilated"})
# source-line tokens that make an upcast *visible* (deliberate casts are
# the AST dtype-thread rule's jurisdiction, not the IR audit's)
_CAST_MARKERS = ("astype", "float32", "f32", "convert", "promote")


@dataclasses.dataclass(frozen=True)
class BufferSite:
    """Where a buffer was born: repo-relative source line + primitive."""
    path: str
    line: int
    primitive: str

    def label(self) -> str:
        return f"{self.path}:{self.line} ({self.primitive})"


@dataclasses.dataclass
class BufferInfo:
    site: BufferSite
    nbytes: int
    shape: Tuple[int, ...]
    dtype: str


@dataclasses.dataclass
class ProgramAudit:
    """One program's walk: the peak estimate and everything it's made of."""
    name: str
    peak_bytes: int
    peak_live: List[BufferInfo]              # live set at the peak point
    site_max_bytes: Dict[BufferSite, int]    # per-site max buffer bytes
    n_eqns: int

    def top_buffers(self, n: int = TOP_N) -> List[BufferInfo]:
        return sorted(self.peak_live, key=lambda b: -b.nbytes)[:n]


def _repo_relative(filename: str) -> str:
    """``/abs/.../src/repro/x.py`` -> ``src/repro/x.py`` (best effort)."""
    norm = filename.replace("\\", "/")
    for anchor in ("src/repro/", "benchmarks/", "examples/", "tests/"):
        idx = norm.find(anchor)
        if idx >= 0:
            return norm[idx:]
    return norm


def _site(eqn: jcore.JaxprEqn) -> BufferSite:
    frame = _siu.user_frame(eqn.source_info.traceback)
    if frame is None:
        return BufferSite("<jax-internal>", 0, eqn.primitive.name)
    return BufferSite(_repo_relative(frame.file_name), frame.start_line,
                      eqn.primitive.name)


def _aval_bytes(aval: Any) -> int:
    try:
        return int(aval.size) * jnp.dtype(aval.dtype).itemsize
    except Exception:     # tokens, refs without layouts, abstract units
        return 0


def _aval_info(aval: Any, site: BufferSite) -> BufferInfo:
    shape = tuple(getattr(aval, "shape", ()))
    dtype = str(getattr(aval, "dtype", "-"))
    return BufferInfo(site, _aval_bytes(aval), shape, dtype)


def _sub_jaxprs(eqn: jcore.JaxprEqn) -> List[jcore.Jaxpr]:
    """Inner jaxprs of a call-like equation (scan/pjit/cond/while/...)."""
    out: List[jcore.Jaxpr] = []
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for x in vals:
            if isinstance(x, jcore.ClosedJaxpr):
                out.append(x.jaxpr)
            elif isinstance(x, jcore.Jaxpr):
                out.append(x)
    return out


def _walk(jaxpr: jcore.Jaxpr, in_bufs: Dict[Any, BufferInfo],
          site_max: Dict[BufferSite, int],
          depth: int = 0) -> Tuple[int, List[BufferInfo]]:
    """Linear-scan liveness over one jaxpr.

    Returns ``(peak_bytes, live_set_at_peak)``; ``site_max`` accumulates
    the largest single buffer each source site ever created (recursively
    — the scaling gate fits per-site exponents from it)."""
    live: Dict[Any, BufferInfo] = dict(in_bufs)
    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                last_use[v] = i
    for v in jaxpr.outvars:
        if isinstance(v, jcore.Var):
            last_use[v] = len(jaxpr.eqns)

    peak = sum(b.nbytes for b in live.values())
    peak_live = list(live.values())
    for i, eqn in enumerate(jaxpr.eqns):
        site = _site(eqn)
        for v in eqn.outvars:
            if isinstance(v, jcore.Var):
                info = _aval_info(v.aval, site)
                live[v] = info
                if info.nbytes > site_max.get(site, 0):
                    site_max[site] = info.nbytes
        inner_extra, inner_live = 0, []
        if depth < 12:
            for sub in _sub_jaxprs(eqn):
                sub_in = {
                    v: _aval_info(v.aval, site)
                    for v in list(sub.invars) + list(sub.constvars)
                    if isinstance(v, jcore.Var)}
                sub_peak, sub_live = _walk(sub, sub_in, site_max, depth + 1)
                # inner inputs are views of outer buffers already counted
                sub_in_bytes = sum(b.nbytes for b in sub_in.values())
                extra = max(0, sub_peak - sub_in_bytes)
                if extra > inner_extra:
                    inner_extra, inner_live = extra, [
                        b for b in sub_live
                        if b.nbytes > 0 and b.site.path != "<jax-internal>"]
        cur = sum(b.nbytes for b in live.values()) + inner_extra
        if cur > peak:
            peak = cur
            peak_live = list(live.values()) + inner_live
        for v in list(live):
            if last_use.get(v, -1) <= i:
                del live[v]
    return peak, peak_live


def trace_program(prog: EngineProgram, k: int) -> jcore.ClosedJaxpr:
    fn, args = prog.build(k)
    return jax.make_jaxpr(fn)(*args)


def audit_program(prog: EngineProgram, k: int = 4,
                  closed: Optional[jcore.ClosedJaxpr] = None) -> ProgramAudit:
    """Trace (or reuse ``closed``) and walk one program at user count K."""
    if closed is None:
        closed = trace_program(prog, k)
    jaxpr = closed.jaxpr
    arg_site = BufferSite("<argument>", 0, "argument")
    in_bufs = {v: _aval_info(v.aval, arg_site)
               for v in list(jaxpr.invars) + list(jaxpr.constvars)
               if isinstance(v, jcore.Var)}
    site_max: Dict[BufferSite, int] = {
        arg_site: max((b.nbytes for b in in_bufs.values()), default=0)}
    n_eqns = sum(1 for _ in _iter_eqns(jaxpr))
    peak, peak_live = _walk(jaxpr, in_bufs, site_max)
    return ProgramAudit(name=prog.name, peak_bytes=peak,
                        peak_live=peak_live, site_max_bytes=site_max,
                        n_eqns=n_eqns)


def _iter_eqns(jaxpr: jcore.Jaxpr, depth: int = 0
               ) -> Iterable[jcore.JaxprEqn]:
    for eqn in jaxpr.eqns:
        yield eqn
        if depth < 12:
            for sub in _sub_jaxprs(eqn):
                yield from _iter_eqns(sub, depth + 1)


# ---------------------------------------------------------------------------
# dtype-promotion audit (bf16 programs only)
# ---------------------------------------------------------------------------

def _float_dtypes(vars_: Iterable[Any]) -> set:
    out = set()
    for v in vars_:
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is not None and jnp.issubdtype(dt, jnp.floating):
            out.add(jnp.dtype(dt))
    return out


def _repo_root() -> Path:
    # src/repro/analysis/ir/jaxpr_audit.py -> four levels up
    return Path(__file__).resolve().parents[4]


@functools.lru_cache(maxsize=4096)
def _source_line(path: str, line: int) -> Optional[str]:
    try:
        lines = (_repo_root() / path).read_text().splitlines()
        return lines[line - 1] if 1 <= line <= len(lines) else None
    except OSError:
        return None


def _visible_cast(site: BufferSite) -> bool:
    """True when the offending source line shows the cast itself.

    Unreadable sites (jax internals, generated code) count as visible —
    the audit only claims *silent* when it can read the line and see
    nothing."""
    text = _source_line(site.path, site.line)
    if text is None:
        return True
    low = text.lower()
    return any(m in low for m in _CAST_MARKERS)


def dtype_promotions(prog: EngineProgram,
                     closed: Optional[jcore.ClosedJaxpr] = None,
                     k: int = 4) -> List[Finding]:
    """f32 tensors born from bf16 operands inside a bf16-policy program."""
    if prog.compute_dtype != "bf16":
        return []
    if closed is None:
        closed = trace_program(prog, k)
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    findings: List[Finding] = []
    seen = set()
    for eqn in _iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if _sub_jaxprs(eqn):
            continue           # calls are audited through their bodies
        if name in _ACCUM_PRIMS and \
                eqn.params.get("preferred_element_type") == jnp.float32:
            continue           # documented f32-accumulator idiom
        if bf16 not in _float_dtypes(eqn.invars):
            continue
        outs = [v for v in eqn.outvars
                if getattr(getattr(v, "aval", None), "dtype", None) == f32]
        if not outs:
            continue
        site = _site(eqn)
        if name == "convert_element_type" and _visible_cast(site):
            continue           # deliberate cast: dtype-thread's business
        key = (site.path, site.line, name)
        if key in seen:
            continue
        seen.add(key)
        shape = tuple(getattr(outs[0].aval, "shape", ()))
        how = ("implicit promotion (jnp inserted the upcast)"
               if name == "convert_element_type"
               else f"{name} mints f32 from bf16 operands")
        findings.append(Finding(
            site.path, site.line, 0, "ir-dtype",
            f"{prog.name}: {how} -> f32{list(shape)} inside a "
            f"compute_dtype=bf16 program — a silent upcast; cast "
            f"explicitly (a visible astype/float32 on the line is "
            f"exempt) or keep the op in bf16"))
    return findings


def run_jaxpr_audit(programs=None, k: int = 4
                    ) -> Tuple[List[Finding], List[ProgramAudit]]:
    """Walk every registry program once: dtype findings + memory audits.

    A program that fails to trace is itself a finding (same convention as
    the eval_shape contract sweep)."""
    from repro.analysis.ir.programs import engine_programs
    findings: List[Finding] = []
    audits: List[ProgramAudit] = []
    for prog in (programs if programs is not None else engine_programs()):
        try:
            closed = trace_program(prog, k)
        except Exception as exc:      # a broken trace IS the finding
            findings.append(Finding(
                prog.path, 1, 0, "ir-trace",
                f"{prog.name}: jaxpr trace failed at K={k}: "
                f"{type(exc).__name__}: {exc}"))
            continue
        audits.append(audit_program(prog, k, closed=closed))
        findings.extend(dtype_promotions(prog, closed=closed, k=k))
    return findings, audits
