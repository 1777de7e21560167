"""Structural parser + static check engine for lowered/compiled XLA
programs.

The compiled-program half of ``bigdl_tpu.analysis``: the AST linter
checks *Python source* before tracing; this module checks the **HLO
text** of a lowered or compiled program before anything executes. The
invariants the repository used to assert with one-off string greps
(donated buffers actually aliased, zero collectives at the windowed
dispatch boundary, f32 islands staying inside the precision policy,
programs fitting HBM) become pluggable, named checks with findings,
severities and suppressions — the same shape as the lint engine, so
``python -m bigdl_tpu.tools.check --programs`` reports them the same
way.

Three layers, all free of jax imports (pure text analysis):

- **Parser** (:func:`parse_hlo`): ``lowered.as_text(dialect="hlo")`` /
  ``compiled.as_text()`` -> :class:`HloModule` — computations (with the
  ENTRY marked), per-op result shapes/dtypes, operands with def-use
  resolution, shardings, metadata, while/cond/fusion sub-computation
  links, and the module-header input/output aliasing + buffer-donor
  tables. Tuple-typed async ``-start`` collectives (the form real TPU
  schedules emit) parse like any other op.
- **Checks** (:func:`hlo_check` registry, built-ins under
  :mod:`bigdl_tpu.analysis.checks`): generator functions over a
  :class:`ProgramSpec` yielding ``(severity, message)``.
- **Runner** (:func:`run_checks`): findings with lint-style
  suppressions (``ProgramSpec.suppress`` names checks sanctioned for
  that program; suppressed findings are retained, not dropped).

:func:`collective_counts` here is the ONE implementation the repo uses;
``parallel.zero.collective_counts`` / ``window_collectives`` are kept
as deprecated shims over it.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import (Callable, Dict, Iterator, List, Optional, Sequence,
                    Set, Tuple)

__all__ = [
    "HloOp", "HloComputation", "HloModule", "parse_hlo",
    "collective_counts", "reduce_scatter_evidence", "COLLECTIVE_OPS",
    "ProgramSpec", "ProgramFinding", "HloCheck", "hlo_check",
    "available_checks", "run_checks", "format_findings",
    "findings_to_json", "hbm_fit",
]

# ------------------------------------------------------------------ shapes

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1,
    "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")


def _parse_shapes(type_text: str) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
    """Every ``dtype[dims]`` leaf in a (possibly tuple) HLO type."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(type_text):
        if dtype not in _DTYPE_BYTES:
            continue
        shape = tuple(int(d) for d in dims.split(",") if d)
        out.append((dtype, shape))
    return tuple(out)


def _shape_bytes(dtype: str, dims: Tuple[int, ...]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n * _DTYPE_BYTES.get(dtype, 4)


def _balanced(text: str, start: int, open_ch: str = "{",
              close_ch: str = "}") -> str:
    """The balanced ``{...}`` (content only) starting at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == open_ch:
            depth += 1
        elif text[i] == close_ch:
            depth -= 1
            if depth == 0:
                return text[start + 1:i]
    return text[start + 1:]


# ------------------------------------------------------------------ the IR

class HloOp:
    """One HLO instruction: result name/type, opcode, operands,
    attributes of interest. Shapes cover tuple-typed results (async
    ``-start`` collectives) — ``shapes`` is a tuple of
    ``(dtype, dims)`` leaves, ``dtype``/``dims`` the first leaf."""

    __slots__ = ("name", "opcode", "result_type", "shapes", "operands",
                 "attrs", "sharding", "metadata", "is_root",
                 "parameter_index", "called", "lineno")

    def __init__(self, name, opcode, result_type, shapes, operands,
                 attrs, sharding, metadata, is_root, parameter_index,
                 called, lineno):
        self.name = name
        self.opcode = opcode
        self.result_type = result_type
        self.shapes = shapes
        self.operands = operands    # operand NAMES (def-use edges)
        self.attrs = attrs          # raw attribute text after operands
        self.sharding = sharding    # raw sharding={...} content or None
        self.metadata = metadata    # {"op_name":..., "source_file":...,
        #                             "source_line":...} (present keys)
        self.is_root = is_root
        self.parameter_index = parameter_index  # int for parameter ops
        self.called = called        # {"body"/"condition"/"calls"/
        #                             "to_apply": computation name}
        self.lineno = lineno

    @property
    def dtype(self) -> Optional[str]:
        return self.shapes[0][0] if self.shapes else None

    @property
    def dims(self) -> Tuple[int, ...]:
        return self.shapes[0][1] if self.shapes else ()

    def result_bytes(self) -> int:
        return sum(_shape_bytes(d, s) for d, s in self.shapes)

    def result_elements(self) -> int:
        total = 0
        for _, dims in self.shapes:
            n = 1
            for d in dims:
                n *= d
            total += n
        return total

    @property
    def replicated(self) -> bool:
        """True when the op carries an explicit ``sharding={replicated}``
        annotation OR no sharding at all (nothing pinned a layout)."""
        return self.sharding is None or self.sharding == "replicated"

    def __repr__(self) -> str:
        return (f"HloOp({self.name!r} = {self.result_type} "
                f"{self.opcode}({', '.join(self.operands)}))")


class HloComputation:
    """One HLO computation (the ENTRY, a while body/condition, a fused
    computation, a reducer)."""

    def __init__(self, name: str, is_entry: bool):
        self.name = name
        self.is_entry = is_entry
        self.ops: List[HloOp] = []
        self.by_name: Dict[str, HloOp] = {}

    def add(self, op: HloOp) -> None:
        self.ops.append(op)
        self.by_name[op.name] = op

    def op(self, name: str) -> Optional[HloOp]:
        return self.by_name.get(name)

    def operand_op(self, op: HloOp, i: int) -> Optional[HloOp]:
        """The defining op of ``op``'s i-th operand (def-use edge within
        this computation), or None for literals/unknown names."""
        if i >= len(op.operands):
            return None
        return self.by_name.get(op.operands[i])

    def operand_dtypes(self, op: HloOp) -> List[Optional[str]]:
        """Result dtype of each operand's defining op (None when the
        operand does not resolve — e.g. a literal)."""
        return [d.dtype if (d := self.by_name.get(nm)) is not None
                else None for nm in op.operands]

    def __repr__(self) -> str:
        tag = "ENTRY " if self.is_entry else ""
        return f"HloComputation({tag}{self.name!r}, {len(self.ops)} ops)"


class HloModule:
    """A parsed HLO module: computations + the header's aliasing and
    donor tables."""

    def __init__(self, name: str, header: str):
        self.name = name
        self.header = header
        self.computations: Dict[str, HloComputation] = {}
        self.entry: Optional[HloComputation] = None
        #: entry-parameter indices the module aliases to an output
        #: (``input_output_alias``) — donation honored via aliasing
        self.aliased_params: Set[int] = set()
        #: entry-parameter indices declared donatable
        #: (``buffer_donor`` — the pre-assignment SPMD form)
        self.donor_params: Set[int] = set()
        self._parse_header(header)

    # ---- header tables ---------------------------------------------------
    def _parse_header(self, header: str) -> None:
        m = re.search(r"input_output_alias=\{", header)
        if m:
            body = _balanced(header, m.end() - 1)
            # entries: "{out,path}: (param, {param_path}[, kind])"
            for pm in re.finditer(r"\}:\s*\(\s*(\d+)", body):
                self.aliased_params.add(int(pm.group(1)))
        m = re.search(r"buffer_donor=\{", header)
        if m:
            body = _balanced(header, m.end() - 1)
            for pm in re.finditer(r"\(\s*(\d+)\s*,", body):
                self.donor_params.add(int(pm.group(1)))

    @property
    def donated_params(self) -> Set[int]:
        """Entry params whose buffers the program may reuse — the union
        of the aliasing table and the donor list."""
        return self.aliased_params | self.donor_params

    # ---- structure -------------------------------------------------------
    def add(self, comp: HloComputation) -> None:
        self.computations[comp.name] = comp
        if comp.is_entry:
            self.entry = comp

    def entry_params(self) -> List[HloOp]:
        """ENTRY ``parameter`` ops, sorted by parameter index."""
        if self.entry is None:
            return []
        params = [op for op in self.entry.ops if op.opcode == "parameter"]
        return sorted(params, key=lambda p: p.parameter_index or 0)

    def find_ops(self, opcode: Optional[str] = None,
                 entry_only: bool = False
                 ) -> Iterator[Tuple[HloComputation, HloOp]]:
        """Iterate ``(computation, op)`` over the module, optionally
        restricted to one opcode / the ENTRY computation."""
        for comp in self.computations.values():
            if entry_only and not comp.is_entry:
                continue
            for op in comp.ops:
                if opcode is None or op.opcode == opcode:
                    yield comp, op

    def while_bodies(self) -> Set[str]:
        """Names of computations used as a ``while`` body (scan/loop
        bodies — where the windowed driver's per-step work lives)."""
        return {op.called["body"] for _, op in self.find_ops("while")
                if "body" in op.called}

    def __repr__(self) -> str:
        return (f"HloModule({self.name!r}, "
                f"{len(self.computations)} computations)")


# ------------------------------------------------------------------ parser

# computation header: optional ENTRY, optional %, optional signature —
# covers scheduled ("%name (args) -> type {") and lowered ("ENTRY main.4
# {") spellings alike
_COMP_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\))?\s*(?:->\s*.+?)?\s*\{\s*$")

# instruction: "[ROOT] %name = TYPE opcode(operands...", the TYPE matched
# lazily because tuple types ("(f32[2,4]{1,0}, f32[16,4]{1,0})") contain
# spaces — the async -start collective form real TPU schedules emit
_OP_RE = re.compile(
    r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S.*?)\s+([a-z][a-z0-9\-]*)\((.*)$")

_NAME_RE = re.compile(r"[A-Za-z_][\w.\-]*")
_CALLED_RE = re.compile(r"\b(body|condition|calls|to_apply)=%?([\w.\-]+)")


def _split_operands(rest: str) -> Tuple[str, str]:
    """``rest`` (text after the opening paren) -> (operand segment,
    attribute text) by balanced-paren scan — operand types can be
    nested tuples (``while((s32[], f32[1]{0}) %t)``)."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i], rest[i + 1:]
    return rest, ""


def _operand_names(segment: str) -> List[str]:
    """Operand result names from an operand segment — ``%name`` refs in
    scheduled text, bare trailing names in lowered text."""
    if "%" in segment:
        return [m.group(1)
                for m in re.finditer(r"%([\w.\-]+)", segment)]
    names = []
    depth = 0
    token = []
    tokens = []
    for ch in segment:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            tokens.append("".join(token))
            token = []
        else:
            token.append(ch)
    tokens.append("".join(token))
    for tok in tokens:
        words = tok.strip().split()
        if not words:
            continue
        m = _NAME_RE.fullmatch(words[-1])
        if m:
            names.append(words[-1])
    return names


def _parse_attrs(attr_text: str):
    sharding = None
    m = re.search(r"\bsharding=\{", attr_text)
    if m:
        sharding = _balanced(attr_text, m.end() - 1).strip()
    metadata: Dict[str, object] = {}
    m = re.search(r'op_name="([^"]*)"', attr_text)
    if m:
        metadata["op_name"] = m.group(1)
    m = re.search(r'source_file="([^"]*)"', attr_text)
    if m:
        metadata["source_file"] = m.group(1)
    m = re.search(r"source_line=(\d+)", attr_text)
    if m:
        metadata["source_line"] = int(m.group(1))
    called = {k: v for k, v in _CALLED_RE.findall(attr_text)}
    return sharding, metadata, called


def parse_hlo(text: str) -> HloModule:
    """Parse HLO text (``compiled.as_text()`` or
    ``lowered.as_text(dialect="hlo")``) into an :class:`HloModule`."""
    lines = text.splitlines()
    name = "module"
    header = ""
    if lines and lines[0].startswith("HloModule"):
        header = lines[0]
        parts = header.split(None, 2)
        if len(parts) >= 2:
            name = parts[1].rstrip(",")
    module = HloModule(name, header)
    comp: Optional[HloComputation] = None
    for lineno, line in enumerate(lines, 1):
        if not line.strip() or line.startswith("HloModule") \
                or line.lstrip().startswith("//"):
            continue
        if comp is not None and line.startswith("}"):
            module.add(comp)
            comp = None
            continue
        if comp is None:
            m = _COMP_RE.match(line)
            if m and not line.startswith(" "):
                comp = HloComputation(m.group(2),
                                      is_entry=bool(m.group(1)))
            continue
        m = _OP_RE.match(line)
        if m is None:
            continue
        is_root, op_name, type_text, opcode, rest = (
            bool(m.group(1)), m.group(2), m.group(3), m.group(4),
            m.group(5))
        operand_seg, attr_text = _split_operands(rest)
        sharding, metadata, called = _parse_attrs(attr_text)
        param_idx = None
        if opcode == "parameter":
            pm = re.match(r"\s*(\d+)", operand_seg)
            if pm:
                param_idx = int(pm.group(1))
        operands = [] if opcode in ("parameter", "constant") \
            else _operand_names(operand_seg)
        comp.add(HloOp(op_name, opcode, type_text.strip(),
                       _parse_shapes(type_text), operands, attr_text,
                       sharding, metadata, is_root, param_idx, called,
                       lineno))
    if comp is not None:  # unterminated tail computation
        module.add(comp)
    return module


def _as_module(program) -> HloModule:
    """Accept an :class:`HloModule`, HLO text, or an object with
    ``as_text()`` (a compiled jit program)."""
    if isinstance(program, HloModule):
        return program
    if isinstance(program, str):
        return parse_hlo(program)
    return parse_hlo(program.as_text())


# ------------------------------------------------------------ collectives

#: ops counted by :func:`collective_counts` — ``dynamic-slice`` is not
#: itself a collective but is counted because XLA CPU lowers
#: reduce-scatter to all-reduce + dynamic-slice (the scatter evidence on
#: that backend is the pair, not the fused op)
COLLECTIVE_OPS = ("all-gather", "reduce-scatter", "all-reduce",
                  "collective-permute", "all-to-all", "dynamic-slice")

#: the subset that is genuinely cross-device communication (what the
#: entry-collective dispatch-boundary contract bans from ENTRY)
COMMUNICATION_OPS = ("all-gather", "reduce-scatter", "all-reduce",
                     "collective-permute", "all-to-all")


def collective_counts(program) -> Dict[str, Dict[str, int]]:
    """Count collective ops, split ENTRY vs everything else (scan/while
    bodies, fusions): ``{"all-gather": {"total": n, "entry": m}, ...}``.

    Async ``-start`` forms count once under their base op (the ``-done``
    twin is never counted), including the tuple-typed result spelling
    real TPU schedules emit. The TPU compiler writes a reduce-scatter
    as a ``fusion`` that calls an ``all-reduce-scatter.N`` computation
    (all-reduce + dynamic-slice inside): the fusion counts as one
    ``reduce-scatter`` where it stands. Accepts HLO text, a parsed
    :class:`HloModule`, or a compiled program object."""
    module = _as_module(program)
    counts = {op: {"total": 0, "entry": 0} for op in COLLECTIVE_OPS}
    for comp in module.computations.values():
        for op in comp.ops:
            base = op.opcode[:-6] if op.opcode.endswith("-start") \
                else op.opcode
            if base == "fusion" and (op.called or {}).get(
                    "calls", "").lstrip("%").startswith(
                        "all-reduce-scatter"):
                base = "reduce-scatter"
            if base not in counts:
                continue
            counts[base]["total"] += 1
            if comp.is_entry:
                counts[base]["entry"] += 1
    return counts


def reduce_scatter_evidence(counts: Dict[str, Dict[str, int]]) -> bool:
    """True when the program reduce-scatters gradients: a
    ``reduce-scatter`` (literal, or the TPU compiler's fused
    ``all-reduce-scatter``), or the CPU lowering's all-reduce +
    dynamic-slice pair."""
    if counts["reduce-scatter"]["total"] > 0:
        return True
    return (counts["all-reduce"]["total"] > 0
            and counts["dynamic-slice"]["total"] > 0)


def hbm_fit(analysis: Dict[str, float],
            budget_bytes: Optional[int]) -> Dict[str, object]:
    """Static HBM feasibility of one program: does ``arguments +
    outputs + temps`` fit ``budget_bytes``? ``analysis`` is the dict
    :func:`bigdl_tpu.telemetry.programs.analyze_compiled` returns (or
    any mapping with ``arg_bytes``/``out_bytes``/``temp_bytes``).

    This is the API the profile-guided autotuner (ROADMAP item 4)
    calls per candidate config: lowering + ``memory_analysis`` only —
    no execution — prunes HBM-infeasible points before anything runs.
    Returns ``{fits, total_bytes, budget_bytes, breakdown}``; a None
    budget always fits (reported, never enforced)."""
    breakdown = {k: float(analysis.get(k, 0.0))
                 for k in ("arg_bytes", "out_bytes", "temp_bytes")}
    total = int(sum(breakdown.values()))
    fits = budget_bytes is None or total <= budget_bytes
    return {"fits": fits, "total_bytes": total,
            "budget_bytes": budget_bytes, "breakdown": breakdown}


# ------------------------------------------------------------ check engine

@dataclass
class ProgramSpec:
    """One program under verification + the contract context its checks
    need. ``module`` is the parsed *compiled* text (aliasing tables,
    collective placement); ``lowered`` the parsed pre-optimization HLO
    (parameter shardings, the policy's dtype intent — backends legalize
    dtypes during compilation, so precision contracts read the lowered
    form). Thresholds are per-program so fixtures and the autotuner can
    tighten them."""

    name: str
    module: Optional[HloModule] = None
    lowered: Optional[HloModule] = None
    #: expected donated leaf count (-1: no donation contract declared)
    donated: int = -1
    #: the steps_per_sync dispatch-boundary contract applies
    window: bool = False
    scan_length: int = 1
    #: a smaller-K build of the same window (scan-dispatch-ratio)
    companion: Optional["ProgramSpec"] = None
    zero_stage: int = 0
    ndev: int = 1
    #: entry-parameter indices the ZeRO config expects sharded
    sharded_params: Tuple[int, ...] = ()
    #: precision policy name compiled into the program (None = f32)
    policy: Optional[str] = None
    compute_dtype: Optional[str] = None
    #: ``memory_analysis`` numbers (arg/out/temp bytes)
    memory: Optional[Dict[str, float]] = None
    hbm_budget: Optional[int] = None
    #: replicated-large-operand threshold (bytes per parameter)
    large_bytes: int = 1 << 20
    #: precision-leak: f32 dot/conv operand threshold (elements)
    dot_elems: int = 4096
    #: precision-leak: giant f32 convert threshold (bytes)
    convert_bytes: int = 16 << 20
    #: checks sanctioned for this program (findings kept, suppressed)
    suppress: Tuple[str, ...] = ()
    #: free-form context (kind, bucket, K ...) carried into reports
    extra: Dict[str, object] = field(default_factory=dict)


@dataclass
class ProgramFinding:
    """One check finding on one program."""

    check: str
    program: str
    severity: str  # "error" | "warning"
    message: str
    suppressed: bool = False

    def format(self) -> str:
        tag = " (suppressed)" if self.suppressed else ""
        return (f"{self.program}: [{self.check}/{self.severity}]{tag} "
                f"{self.message}")

    def to_dict(self) -> dict:
        return {"check": self.check, "program": self.program,
                "severity": self.severity, "message": self.message,
                "suppressed": self.suppressed}


@dataclass
class HloCheck:
    """A registered program check: ``fn(spec)`` yields
    ``(severity, message)``."""

    name: str
    description: str
    fn: Callable[[ProgramSpec], Iterator[Tuple[str, str]]]


_CHECKS: Dict[str, HloCheck] = {}


def hlo_check(name: str, description: str):
    """Decorator registering a compiled-program check under ``name``
    (the HLO twin of :func:`bigdl_tpu.analysis.lint.rule`)."""
    def deco(fn):
        if name in _CHECKS:
            raise ValueError(f"duplicate hlo check {name!r}")
        _CHECKS[name] = HloCheck(name, description, fn)
        return fn
    return deco


def available_checks() -> List[HloCheck]:
    """All registered checks, sorted by name (importing the
    built-ins)."""
    import bigdl_tpu.analysis.checks  # noqa: F401  registers on import
    return [_CHECKS[k] for k in sorted(_CHECKS)]


def run_checks(specs: Sequence[ProgramSpec],
               checks: Optional[Sequence[str]] = None
               ) -> List[ProgramFinding]:
    """Run checks over every program spec; returns findings (suppressed
    ones flagged, not dropped). ``checks`` restricts to a named subset
    (unknown names raise KeyError, like the lint engine)."""
    import bigdl_tpu.analysis.checks  # noqa: F401  registers built-ins
    selected = [_CHECKS[c] for c in checks] if checks else \
        [_CHECKS[k] for k in sorted(_CHECKS)]
    findings: List[ProgramFinding] = []
    for spec in specs:
        for check in selected:
            for severity, message in check.fn(spec):
                findings.append(ProgramFinding(
                    check.name, spec.name, severity, message,
                    suppressed=check.name in spec.suppress))
    findings.sort(key=lambda f: (f.program, f.check, f.message))
    return findings


def format_findings(findings: Sequence[ProgramFinding],
                    programs: int = 0,
                    show_suppressed: bool = False) -> str:
    """Human-readable report, lint-style."""
    shown = [f for f in findings if show_suppressed or not f.suppressed]
    lines = [f.format() for f in shown]
    active = sum(1 for f in findings if not f.suppressed)
    muted = len(findings) - active
    lines.append(
        f"{active} program finding{'s' if active != 1 else ''}"
        f" ({muted} suppressed) across {programs} programs")
    return "\n".join(lines)


def findings_to_json(findings: Sequence[ProgramFinding]) -> str:
    """Machine-readable report (stable keys; includes suppressed)."""
    return json.dumps([f.to_dict() for f in findings], indent=2)
