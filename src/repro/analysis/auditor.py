"""Program auditor: structured reports over the programs this repo compiles.

The structural guarantees the paper's "matrix operations, no iterations"
claim rests on — one (k, m) psum per ``flat_sharded`` apply pass, no
all-gather of a parameter leaf, f32 accumulation under bf16 sketch storage,
no host round-trips on the hot path — used to live as substring greps over
lowered HLO text. ``audit`` replaces the grep: it lowers a function and
walks **three layers** of the same program,

  * the **jaxpr** (recursively, through pjit/scan/shard_map/custom_vjp/
    pallas_call sub-jaxprs): collective eqns with their mesh axes and
    reduction dtypes, ``dot_general``/conv accumulation dtypes
    (``preferred_element_type`` vs operand dtypes), host callbacks, and
    ``custom_vjp`` boundaries;
  * the **lowered StableHLO** text: collective op counts (shard_map
    collectives appear here exactly as written, pre-optimization), host
    callback custom-calls, and materialized constant sizes;
  * optionally the **compiled HLO** text (``compile=True``): the
    collectives that actually execute, including any GSPMD-inserted
    all-gathers that exist in no earlier layer (byte totals via
    ``repro.launch.analysis.collective_bytes`` — the same parser the
    roofline dry-runs use).

A :class:`Contract` is the declarative check over the resulting
:class:`ProgramReport`: ``Contract(no_all_gather=True,
exact_collectives={'psum': 1}, min_accum_dtype='float32')`` renders precise
violations (op kind, shape, dtype, mesh axes, source layer) instead of a
substring miss. See docs/static-analysis.md.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Mapping

import jax

__all__ = ['OpRecord', 'DotRecord', 'TransferRecord', 'ConstRecord',
           'ProgramReport', 'Contract', 'ContractViolation', 'Violation',
           'audit', 'audit_jaxpr', 'canonical_collective']

# ---------------------------------------------------------------------------
# Canonical collective naming.  Three spellings reach us: jaxpr primitive
# names (psum / psum2 / all_gather ...; ``lax.psum`` inside ``shard_map``
# traces as psum_invariant), StableHLO ops (stablehlo.all_reduce),
# and compiled-HLO ops (all-reduce).  Contracts accept any alias.
# ---------------------------------------------------------------------------
_CANONICAL = {
    'psum': 'all-reduce', 'psum2': 'all-reduce', 'psum_invariant': 'all-reduce',
    'all_reduce': 'all-reduce',
    'all-reduce': 'all-reduce', 'pmax': 'all-reduce', 'pmin': 'all-reduce',
    'all_gather': 'all-gather', 'all-gather': 'all-gather',
    'reduce_scatter': 'reduce-scatter', 'reduce-scatter': 'reduce-scatter',
    'psum_scatter': 'reduce-scatter',
    'all_to_all': 'all-to-all', 'all-to-all': 'all-to-all',
    'ppermute': 'collective-permute', 'collective_permute':
        'collective-permute', 'collective-permute': 'collective-permute',
}

# jaxpr primitives that are host round-trips
_CALLBACK_PRIMS = ('pure_callback', 'io_callback', 'debug_callback',
                   'callback')
# StableHLO custom_call targets that are host round-trips (sharding
# annotations etc. are also custom_calls — only these leave the device)
_HOST_CALL_TARGETS = ('xla_python_cpu_callback', 'xla_ffi_python_cpu_callback',
                      'xla_python_gpu_callback', 'xla_ffi_partitioned_callback')

_CUSTOM_VJP_PRIMS = ('custom_vjp_call', 'custom_vjp_call_jaxpr')

# float dtype -> precision rank for min_accum_dtype ordering
_FLOAT_BITS = {'bfloat16': 16, 'float16': 16, 'float8_e4m3fn': 8,
               'float8_e5m2': 8, 'float32': 32, 'float64': 64}


def canonical_collective(name: str) -> str:
    """Canonical kind for any spelling ('psum' → 'all-reduce'); unknown
    names pass through unchanged so contracts fail loudly, not silently."""
    return _CANONICAL.get(name, name)


def _float_bits(dtype: Any) -> int | None:
    return _FLOAT_BITS.get(str(dtype))


# ---------------------------------------------------------------------------
# Report records
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class OpRecord:
    """One collective op: canonical kind, result dtype/shape, where it was
    seen ('jaxpr' | 'stablehlo' | 'hlo'), and detail (mesh axes for jaxpr
    collectives, the source line for HLO ones)."""
    kind: str
    dtype: str
    shape: tuple[int, ...]
    source: str
    detail: str = ''

    def render(self) -> str:
        extra = f' [{self.detail}]' if self.detail else ''
        return (f'{self.kind} {self.dtype}{list(self.shape)} '
                f'({self.source}){extra}')


@dataclasses.dataclass(frozen=True)
class DotRecord:
    """One dot/conv: operand dtypes and the dtype it accumulates in
    (``preferred_element_type`` when set, else the output dtype)."""
    primitive: str
    operand_dtypes: tuple[str, ...]
    accum_dtype: str
    out_shape: tuple[int, ...]
    preferred: bool          # accumulation dtype was explicitly requested

    def render(self) -> str:
        pref = 'preferred' if self.preferred else 'implicit'
        return (f'{self.primitive}({" x ".join(self.operand_dtypes)}) '
                f'-> {self.accum_dtype}{list(self.out_shape)} ({pref})')


@dataclasses.dataclass(frozen=True)
class TransferRecord:
    """One host round-trip (callback primitive or host custom-call)."""
    kind: str
    source: str
    detail: str = ''

    def render(self) -> str:
        extra = f' [{self.detail}]' if self.detail else ''
        return f'{self.kind} ({self.source}){extra}'


@dataclasses.dataclass(frozen=True)
class ConstRecord:
    """One materialized StableHLO constant."""
    dtype: str
    shape: tuple[int, ...]
    nbytes: int


@dataclasses.dataclass
class ProgramReport:
    """Everything a :class:`Contract` checks, from one lowered program."""
    collectives: list[OpRecord]
    dots: list[DotRecord]
    host_transfers: list[TransferRecord]
    custom_vjp_calls: int
    constants: list[ConstRecord]
    stablehlo: str = ''
    hlo: str | None = None
    collective_nbytes: dict[str, int] | None = None   # compiled HLO only

    def records(self, kind: str | None = None,
                source: str | None = None) -> list[OpRecord]:
        kind = canonical_collective(kind) if kind is not None else None
        return [r for r in self.collectives
                if (kind is None or r.kind == kind)
                and (source is None or r.source == source)]

    def count(self, kind: str, source: str = 'stablehlo') -> int:
        """Collective count by canonical kind (aliases accepted) in one
        source layer — 'stablehlo' is the stable pre-optimization count."""
        return len(self.records(kind, source))

    def counts(self, source: str = 'stablehlo') -> dict[str, int]:
        out: dict[str, int] = {}
        for r in self.records(source=source):
            out[r.kind] = out.get(r.kind, 0) + 1
        return out

    @property
    def sources(self) -> tuple[str, ...]:
        seen = []
        for s in ('jaxpr', 'stablehlo', 'hlo'):
            if s == 'hlo' and self.hlo is None:
                continue
            seen.append(s)
        return tuple(seen)

    def constant_bytes(self) -> int:
        return sum(c.nbytes for c in self.constants)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------
def _sub_jaxprs(params: Mapping[str, Any]):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for value in params.values():
        items = value if isinstance(value, (list, tuple)) else (value,)
        for item in items:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def _walk_jaxpr(jaxpr, report: ProgramReport) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _CANONICAL:
            axes = eqn.params.get('axes') or eqn.params.get('axis_name')
            out = eqn.outvars[0].aval
            report.collectives.append(OpRecord(
                kind=canonical_collective(name), dtype=str(out.dtype),
                shape=tuple(out.shape), source='jaxpr',
                detail=f'axes={tuple(axes)}' if axes else ''))
        elif name in ('dot_general', 'conv_general_dilated'):
            out = eqn.outvars[0].aval
            pet = eqn.params.get('preferred_element_type')
            report.dots.append(DotRecord(
                primitive=name,
                operand_dtypes=tuple(str(v.aval.dtype) for v in eqn.invars),
                accum_dtype=str(pet) if pet is not None else str(out.dtype),
                out_shape=tuple(out.shape), preferred=pet is not None))
        elif name in _CALLBACK_PRIMS:
            report.host_transfers.append(TransferRecord(
                kind=name, source='jaxpr'))
        if name in _CUSTOM_VJP_PRIMS:
            report.custom_vjp_calls += 1
        _walk_params = eqn.params
        for sub in _sub_jaxprs(_walk_params):
            _walk_jaxpr(sub, report)


# ---------------------------------------------------------------------------
# StableHLO / compiled-HLO text parsing
# ---------------------------------------------------------------------------
_STABLEHLO_COLL_RE = re.compile(
    r'stablehlo\.(all_reduce|all_gather|reduce_scatter|all_to_all|'
    r'collective_permute)\b')
_TENSOR_RE = re.compile(r'tensor<((?:\d+x)*)([a-z0-9_]+)>')
_CONST_RE = re.compile(r'stablehlo\.constant\b')
_CUSTOM_CALL_RE = re.compile(r'stablehlo\.custom_call\s+@(\w+)')

_MLIR_DTYPE_BYTES = {'f64': 8, 'f32': 4, 'f16': 2, 'bf16': 2, 'i64': 8,
                     'ui64': 8, 'i32': 4, 'ui32': 4, 'i16': 2, 'ui16': 2,
                     'i8': 1, 'ui8': 1, 'i1': 1, 'f8e4m3fn': 1, 'f8e5m2': 1}

HLO_COLLECTIVES = ('all-reduce', 'all-gather', 'reduce-scatter',
                   'all-to-all', 'collective-permute')
_HLO_LINE_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%[\w.-]+\s*=\s*([^=]+?)\s+('
    + '|'.join(HLO_COLLECTIVES) + r')(?:-start|-done)?\(')
#: (dtype, dims) of each array type in an HLO type string; a TPU layout
#: suffix such as ``{1,0:T(4,128)S(1)}`` follows the dims and is ignored
HLO_SHAPE_RE = re.compile(r'(\w+)\[([\d,]*)\]')


def hlo_collective_ops(text: str):
    """(kind, result type string, line) of every collective in compiled
    HLO text. An async pair counts once: ``-start`` is kept, ``-done``
    (the same transfer) skipped. The one reader of the collective-line
    format, shared with ``repro.launch.analysis.collective_bytes``."""
    for line in text.splitlines():
        if '-done(' in line:
            continue
        m = _HLO_LINE_RE.match(line)
        if m:
            yield m.group(2), m.group(1), line


def _tensor_on_line(line: str) -> tuple[tuple[int, ...], str]:
    """Best-effort (shape, dtype) from an MLIR line: the result type after
    '->' when the type signature is on this line, else unknown (ops with
    regions — all_reduce — close their signature lines later; attribute
    tensors like replica_groups must not be mistaken for the result)."""
    _, arrow, result = line.partition('->')
    if arrow:
        matches = _TENSOR_RE.findall(result)
    elif 'constant' in line:
        matches = _TENSOR_RE.findall(line)
    else:
        matches = []
    if not matches:
        return (), '?'
    dims, dtype = matches[-1]
    shape = tuple(int(d) for d in dims.split('x') if d)
    return shape, dtype


def _parse_stablehlo(text: str, report: ProgramReport) -> None:
    for line in text.splitlines():
        m = _STABLEHLO_COLL_RE.search(line)
        if m:
            shape, dtype = _tensor_on_line(line)
            report.collectives.append(OpRecord(
                kind=canonical_collective(m.group(1)), dtype=dtype,
                shape=shape, source='stablehlo', detail=line.strip()[:120]))
        cc = _CUSTOM_CALL_RE.search(line)
        if cc and cc.group(1) in _HOST_CALL_TARGETS:
            report.host_transfers.append(TransferRecord(
                kind=cc.group(1), source='stablehlo',
                detail=line.strip()[:120]))
        if _CONST_RE.search(line):
            shape, dtype = _tensor_on_line(line)
            n = 1
            for d in shape:
                n *= d
            report.constants.append(ConstRecord(
                dtype=dtype, shape=shape,
                nbytes=n * _MLIR_DTYPE_BYTES.get(dtype, 4)))


def _parse_hlo(text: str, report: ProgramReport) -> None:
    for kind, type_str, line in hlo_collective_ops(text):
        sm = HLO_SHAPE_RE.search(type_str)
        shape: tuple[int, ...] = ()
        dtype = '?'
        if sm:
            dtype = sm.group(1)
            shape = tuple(int(d) for d in sm.group(2).split(',') if d)
        report.collectives.append(OpRecord(
            kind=canonical_collective(kind), dtype=dtype, shape=shape,
            source='hlo', detail=line.strip()[:120]))


# ---------------------------------------------------------------------------
# audit
# ---------------------------------------------------------------------------
def audit_jaxpr(closed_jaxpr) -> ProgramReport:
    """Walk an already-built ClosedJaxpr into a (text-less) report."""
    report = ProgramReport(collectives=[], dots=[], host_transfers=[],
                           custom_vjp_calls=0, constants=[])
    _walk_jaxpr(closed_jaxpr.jaxpr, report)
    return report


def audit(fn: Callable, *args, compile: bool = False,
          static_argnums=(), **kwargs) -> ProgramReport:
    """Lower ``fn(*args, **kwargs)`` and walk jaxpr + StableHLO (and, with
    ``compile=True``, the compiled HLO — the only layer where
    GSPMD-inserted collectives exist) into a :class:`ProgramReport`.

    ``fn`` is traced as-is (wrap in ``functools.partial`` for static
    configuration); sharded operands placed with ``jax.device_put`` carry
    their shardings into the lowering exactly as ``jax.jit(fn).lower``
    would see them.
    """
    jitted = jax.jit(fn, static_argnums=static_argnums)
    report = ProgramReport(collectives=[], dots=[], host_transfers=[],
                           custom_vjp_calls=0, constants=[])
    _walk_jaxpr(jax.make_jaxpr(fn, static_argnums=static_argnums)(
        *args, **kwargs).jaxpr, report)
    lowered = jitted.lower(*args, **kwargs)
    report.stablehlo = lowered.as_text()
    _parse_stablehlo(report.stablehlo, report)
    if compile:
        report.hlo = lowered.compile().as_text()
        _parse_hlo(report.hlo, report)
        from repro.launch.analysis import collective_bytes
        report.collective_nbytes = collective_bytes(report.hlo)['bytes']
    return report


# ---------------------------------------------------------------------------
# Contracts
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Violation:
    """One broken guarantee, renderable with full op context."""
    rule: str
    message: str

    def __str__(self) -> str:
        return f'[{self.rule}] {self.message}'


class ContractViolation(AssertionError):
    """Raised by ``Contract.enforce`` — carries every violation."""

    def __init__(self, contract: 'Contract', violations: list[Violation]):
        self.contract = contract
        self.violations = violations
        name = contract.name or 'program contract'
        super().__init__(
            f'{name}: {len(violations)} violation(s)\n  '
            + '\n  '.join(str(v) for v in violations))


@dataclasses.dataclass(frozen=True)
class Contract:
    """A declarative set of structural guarantees over a ProgramReport.

    Fields (all optional — unset fields check nothing):

    ``no_all_gather``
        No all-gather in ANY layer (lowered StableHLO *and*, when the
        report was compiled, optimized HLO — where GSPMD inserts the
        gathers that exist nowhere else).
    ``max_collectives`` / ``exact_collectives`` / ``min_collectives``
        {kind: count} bounds on the **lowered StableHLO** collective
        counts (the stable pre-optimization layer — compiled-HLO op counts
        move under fusion). Kinds accept aliases: 'psum' == 'all-reduce'.
    ``min_accum_dtype``
        Every float dot/conv must accumulate in at least this dtype
        (bf16-operand dots must carry ``preferred_element_type``).
    ``min_reduction_dtype``
        Every collective's result dtype must be at least this wide
        (bf16 operands may ride a psum only after widening to f32).
    ``no_host_transfer``
        No callback primitives / host custom-calls anywhere.
    ``max_constant_bytes``
        Cap on total bytes of materialized StableHLO constants (a baked-in
        operand that should have been an argument).
    """
    name: str = ''
    no_all_gather: bool = False
    max_collectives: Mapping[str, int] | None = None
    exact_collectives: Mapping[str, int] | None = None
    min_collectives: Mapping[str, int] | None = None
    min_accum_dtype: str | None = None
    min_reduction_dtype: str | None = None
    no_host_transfer: bool = False
    max_constant_bytes: int | None = None

    # ------------------------------------------------------------- checks
    def check(self, report: ProgramReport) -> list[Violation]:
        """Every violated guarantee, precisely rendered; [] when clean."""
        v: list[Violation] = []
        if self.no_all_gather:
            for src in ('stablehlo', 'hlo'):
                for rec in report.records('all-gather', src):
                    v.append(Violation(
                        'no_all_gather',
                        f'all-gather of {rec.dtype}{list(rec.shape)} in '
                        f'{src}: {rec.detail or rec.render()}'))
        for bound_name, bounds, cmp in (
                ('max_collectives', self.max_collectives, 'max'),
                ('exact_collectives', self.exact_collectives, 'exact'),
                ('min_collectives', self.min_collectives, 'min')):
            if not bounds:
                continue
            counts = report.counts('stablehlo')
            for kind, bound in bounds.items():
                got = counts.get(canonical_collective(kind), 0)
                bad = (got > bound if cmp == 'max'
                       else got != bound if cmp == 'exact'
                       else got < bound)
                if bad:
                    ops = ', '.join(
                        r.render() for r in
                        report.records(kind, 'stablehlo')) or 'none'
                    v.append(Violation(bound_name, (
                        f'{canonical_collective(kind)}: {got} in lowered '
                        f'StableHLO, {cmp} {bound} allowed; ops: {ops}')))
        if self.min_accum_dtype is not None:
            need = _float_bits(self.min_accum_dtype)
            for dot in report.dots:
                bits = _float_bits(dot.accum_dtype)
                if bits is not None and need is not None and bits < need:
                    v.append(Violation(
                        'min_accum_dtype',
                        f'{dot.render()} accumulates below '
                        f'{self.min_accum_dtype}'))
        if self.min_reduction_dtype is not None:
            need = _float_bits(self.min_reduction_dtype)
            for rec in report.records(source='jaxpr'):
                bits = _float_bits(rec.dtype)
                if bits is not None and need is not None and bits < need:
                    v.append(Violation(
                        'min_reduction_dtype',
                        f'{rec.render()} reduces below '
                        f'{self.min_reduction_dtype}'))
        if self.no_host_transfer and report.host_transfers:
            for t in report.host_transfers:
                v.append(Violation('no_host_transfer',
                                   f'host round-trip: {t.render()}'))
        if self.max_constant_bytes is not None:
            total = report.constant_bytes()
            if total > self.max_constant_bytes:
                big = sorted(report.constants, key=lambda c: -c.nbytes)[:3]
                v.append(Violation('max_constant_bytes', (
                    f'{total} bytes of baked constants '
                    f'(max {self.max_constant_bytes}); largest: '
                    + ', '.join(f'{c.dtype}{list(c.shape)}' for c in big))))
        return v

    def enforce(self, report: ProgramReport) -> ProgramReport:
        """Raise :class:`ContractViolation` on any violation; returns the
        report so audits chain."""
        violations = self.check(report)
        if violations:
            raise ContractViolation(self, violations)
        return report

    def check_fn(self, fn: Callable, *args, compile: bool = False,
                 **kwargs) -> ProgramReport:
        """``audit`` + ``enforce`` in one call."""
        return self.enforce(audit(fn, *args, compile=compile, **kwargs))
