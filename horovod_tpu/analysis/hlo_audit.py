"""Structured auditor for compiled/lowered XLA programs (hvt-lint v2,
layer 2).

Every compiled-program invariant the framework actually relies on —
exactly one gradient reduction per optimizer step (PR 4), wire dtype on
the DCN hop (PR 7), donation aliasing, the overlap peel — used to live
as copy-pasted HLO-text greps in three test files. This
module is the single implementation: a small parser over the two text
dialects jax emits (lowered StableHLO from ``.lower().as_text()``,
post-optimization HLO from ``.compile().as_text()``) exposing the ops as
data, plus an `assert_program` API whose failures print a structured
diff instead of a regex mismatch.

The load-bearing discrimination: cross-worker
GRADIENT traffic is

* any non-scalar all-reduce — scalar all-reduces are the loss/accuracy
  metric means, which exist on every path; and
* any rank >= 2 all-gather — the quantized (int8/fp8) wire reduces as a
  gather-sum, one PAYLOAD gather per bucket (a 1-D bucket stacked over
  shards), while the per-bucket f32 scale rides a separate rank-1
  gather (one scalar per shard, noise bytes) that must not inflate the
  count.

The ZeRO-1 composed step (PR 10) adds the SCATTER-form discrimination
(`scatter_reductions`): non-scalar reduce-scatters plus rank >= 2
all-to-alls — the quantized wire's reduce-scatter hop is an all-to-all
with receiver-side f32 summation — with the `scatter-reduction` /
`scatters=N` expectation asserting no full-payload all-reduce survives
anywhere in the program. Since the per-bucket overlapped schedule
(PR 12) the scatter buckets are leaf-aligned and issue bucket-by-bucket
inside the peeled backward, with the tail-family (non-divisible) leaves
merged onto the same buckets — `scatters=N` therefore counts exactly
the bucket count (N == 1 for the canonical probe at the default fusion
threshold), and the small rank-1 all-gather returning the tail columns
is deliberately outside every count (it is the second shot of the
tail's two-shot all-reduce, not a reduction).

Deliberately stdlib-only (`re`/`dataclasses`): the lint/audit CLIs and
the earliest CI hooks import this without jax. Only `step_probe` (which
produces the text) touches jax.
"""

from __future__ import annotations

import dataclasses
import re

__all__ = [
    "CollectiveOp",
    "ProgramAuditError",
    "ProgramExpectation",
    "ReductionHost",
    "ScheduledReduction",
    "assert_program",
    "asynchronous_share",
    "audit",
    "collective_ops",
    "donated_args",
    "gradient_reductions",
    "op_bytes",
    "op_bytes_by_kind",
    "payload_alltoalls",
    "reduction_schedule",
    "scatter_reductions",
    "scope_path",
    "while_bodies",
    "while_count",
    "wire_dtype",
]


# --- the parsed op ----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One cross-device collective in a program's text.

    ``index`` is the op's position among the program's collectives in
    TEXT order — the submission (channel) order every rank must agree
    on; ``dtype`` is the canonical element type of the result payload
    (``i8``, ``f8e4m3``, ``bf16``, ``f32``, ...), identical for both
    dialects (HLO spells int8 ``s8``, StableHLO ``i8``)."""

    kind: str             # "all-reduce" | "all-gather" | "reduce-scatter" | ...
    dtype: str
    shape: tuple
    line: int             # 1-based line in the source text
    index: int

    @property
    def rank(self) -> int:
        return len(self.shape)

    @property
    def scalar(self) -> bool:
        return not self.shape

    def describe(self) -> str:
        dims = "x".join(str(d) for d in self.shape)
        return (
            f"[{self.index}] {self.kind} {self.dtype}"
            f"[{dims}] (line {self.line})"
        )


# --- dtype canonicalization -------------------------------------------------

_DTYPE_CANON = {
    "s8": "i8", "u8": "u8", "si8": "i8",
    "f8e4m3fn": "f8e4m3", "f8e4m3": "f8e4m3",
    "f8e5m2": "f8e5m2", "f8e5m2fn": "f8e5m2",
}

# What a wire/compression NAME (DistributedOptimizer(compression=...),
# HVT_COMPRESSION) means as a payload element type.
WIRE_DTYPES = {
    "int8": "i8", "i8": "i8",
    "fp8": "f8e4m3", "f8": "f8e4m3", "f8e4m3": "f8e4m3",
    "bf16": "bf16",
    "fp16": "f16", "f16": "f16",
    "none": "f32", "f32": "f32", "float32": "f32",
}


def _canon_dtype(raw: str) -> str:
    return _DTYPE_CANON.get(raw.lower(), raw.lower())


def wire_dtype(name: str) -> str:
    """Canonical payload element type for a compression/wire name."""
    try:
        return WIRE_DTYPES[name.strip().lower()]
    except KeyError:
        raise ValueError(
            f"unknown wire {name!r} — one of {sorted(WIRE_DTYPES)}"
        ) from None


# --- parsers ----------------------------------------------------------------

_KINDS = "all_reduce|all_gather|reduce_scatter|all_to_all|collective_permute"

# StableHLO prints the op's attrs (and a reduction region) first and the
# type signature LAST, possibly many lines later:
#   %177 = "stablehlo.all_reduce"(%112) <{...}> ({ region }) :
#       (tensor<2410xf32>) -> tensor<2410xf32>
# so the result type is the first `-> tensor<...>` after the op token
# (tuple results open with `-> (tensor<...>`).
_STABLEHLO_RE = re.compile(
    rf"stablehlo\.({_KINDS})\b.*?->\s*\(?\s*tensor<([^>]*)>", re.S
)

# Post-optimization HLO puts the result type BEFORE the op name on the
# defining line:
#   %all-reduce.6 = f32[2410]{0} all-reduce(f32[2410]{0} %x), channel_id=1
#   %ag = (s8[...], s8[...]) all-gather-start(...)
# `-done` is the same op's completion and must not double-count.
_HLO_RE = re.compile(
    r"=\s*(\([^=]*?\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_HLO_TYPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _parse_tensor_spec(spec: str) -> tuple[str, tuple]:
    """``'8x301xi8'`` -> ('i8', (8, 301)); ``'f32'`` -> ('f32', ())."""
    parts = spec.strip().split("x")
    dims = []
    for p in parts:
        if p.isdigit():
            dims.append(int(p))
        else:
            return _canon_dtype(p), tuple(dims)
    return _canon_dtype(parts[-1]), tuple(dims[:-1])


def _parse_stablehlo(text: str) -> list[CollectiveOp]:
    ops = []
    for m in _STABLEHLO_RE.finditer(text):
        dtype, shape = _parse_tensor_spec(m.group(2))
        ops.append(CollectiveOp(
            kind=m.group(1).replace("_", "-"), dtype=dtype, shape=shape,
            line=text.count("\n", 0, m.start()) + 1, index=len(ops),
        ))
    return ops


def _parse_hlo(text: str) -> list[CollectiveOp]:
    ops = []
    for i, line in enumerate(text.splitlines(), start=1):
        if "-done" in line:
            continue
        m = _HLO_RE.search(line)
        if not m:
            continue
        tm = _HLO_TYPE_RE.search(m.group(1))
        if not tm:
            continue
        dims = tuple(
            int(d) for d in tm.group(2).split(",") if d.strip().isdigit()
        )
        ops.append(CollectiveOp(
            kind=m.group(2), dtype=_canon_dtype(tm.group(1)), shape=dims,
            line=i, index=len(ops),
        ))
    return ops


def collective_ops(text: str) -> list[CollectiveOp]:
    """Every cross-device collective in the program text, in submission
    (channel) order. Dialect auto-detected."""
    if "stablehlo." in text:
        return _parse_stablehlo(text)
    return _parse_hlo(text)


def gradient_reductions(text) -> list[CollectiveOp]:
    """The GRADIENT-traffic collectives (see module docstring): non-
    scalar all-reduces plus rank >= 2 all-gathers (quantized-wire payload
    gathers; rank-1 scale gathers excluded). Accepts program text or a
    pre-parsed op list."""
    ops = collective_ops(text) if isinstance(text, str) else text
    out = []
    for op in ops:
        if op.kind == "all-reduce" and not op.scalar:
            out.append(op)
        elif op.kind == "all-gather" and op.rank >= 2:
            out.append(op)
        elif op.kind == "reduce-scatter" and not op.scalar:
            out.append(op)
    return out


def scatter_reductions(text) -> list[CollectiveOp]:
    """The SCATTER-form gradient reductions: non-scalar reduce-scatters
    plus rank >= 2 all-to-alls (the quantized wire expresses its
    reduce-scatter hop as an all-to-all with receiver-side f32
    summation — sub-16-bit partial sums must never exist on the wire).
    The ZeRO-1 composed step (``Trainer(shard_update=True)`` with
    accumulation/compression) must reduce THIS way: one bucketed group
    of these per optimizer step, and no full-payload all-reduce
    anywhere. Accepts program text or a pre-parsed op list.

    NOTE: check the LOWERED StableHLO — it carries only the explicit
    (shard_map-placed) collectives, so the sharded update's implicit
    parameter all-gather (a GSPMD artifact of the compiled program)
    cannot pollute the count."""
    ops = collective_ops(text) if isinstance(text, str) else text
    return [
        op for op in ops
        if (op.kind == "reduce-scatter" and not op.scalar)
        or (op.kind == "all-to-all" and op.rank >= 2)
    ]


def payload_alltoalls(text) -> list[CollectiveOp]:
    """The PAYLOAD all-to-alls: rank >= 2 — the EP dispatch/combine wire
    (`collectives.all_to_all`) and the quantized wire's reduce-scatter
    shot alike. Rank-1 all-to-alls are scale/column movement (the
    quantized wire's per-bucket f32 scales, a tail-span column shuffle)
    and are excluded, the same discrimination every other count here
    applies to all-gathers. Both dialects. Accepts program text or a
    pre-parsed op list."""
    ops = collective_ops(text) if isinstance(text, str) else text
    return [op for op in ops if op.kind == "all-to-all" and op.rank >= 2]


def _wire_payload_ops(ops) -> list[CollectiveOp]:
    """Every op whose payload must carry the wire dtype: the gradient
    reductions plus the quantized wire's rank >= 2 all-to-alls (rank-1
    scale gathers stay excluded, as everywhere)."""
    grads = gradient_reductions(ops)
    a2a = [
        op for op in ops
        if op.kind == "all-to-all" and op.rank >= 2 and op not in grads
    ]
    return sorted(grads + a2a, key=lambda op: op.index)


#: Payload element sizes for `op_bytes` (canonical dtype -> bytes).
_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4, "i32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "i8": 1, "u8": 1, "s8": 1, "f8e4m3": 1, "f8e5m2": 1,
    "pred": 1, "i1": 1,
}


def op_bytes(op: CollectiveOp) -> int:
    """Payload bytes of one collective's RESULT (elements x element
    size) — the structural bytes-on-wire accounting.
    Unknown element types count 4 bytes (the f32 default)."""
    return _nbytes(op.dtype, op.shape)


def _nbytes(dtype: str, shape: tuple) -> int:
    n = 1
    for d in shape:
        n *= d
    return n * _DTYPE_BYTES.get(dtype, 4)


def op_bytes_by_kind(ops) -> dict:
    """Per-kind payload-byte totals over the program's PAYLOAD
    collectives (non-scalar reductions, rank >= 2 gathers/all-to-alls —
    the same discrimination as the counts; scale noise excluded). The
    expectation-diff context: when a count expectation fails, WHERE the
    wire bytes actually went is the first question."""
    if isinstance(ops, str):
        ops = collective_ops(ops)
    out: dict = {}
    for op in ops:
        payload = (
            (op.kind in ("all-reduce", "reduce-scatter") and not op.scalar)
            or (op.kind in ("all-gather", "all-to-all") and op.rank >= 2)
        )
        if payload:
            out[op.kind] = out.get(op.kind, 0) + op_bytes(op)
    return out


_HLO_WHILE_RE = re.compile(r"=\s*[^=]*\bwhile\(")


def while_count(text: str) -> int:
    """Loop (scan) ops in the program — the overlap peel's structural
    witness (PR 7: the peeled K=2 step has strictly fewer)."""
    if "stablehlo." in text:
        return text.count("stablehlo.while")
    return sum(
        1 for line in text.splitlines() if _HLO_WHILE_RE.search(line)
    )


_HLO_COMPUTATION_RE = re.compile(
    r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)^\}", re.M | re.S
)
_HLO_CALLEE_RE = re.compile(
    r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)"
)


def while_bodies(text: str, scope: str = "") -> list[str]:
    """Compiled HLO: the text of each loop's body whose `while`
    instruction names ``scope`` in its metadata (every loop when empty),
    with every computation the body calls — fusions, nested loops,
    reducers — appended. What "nothing crosses the chips inside the
    loop" has to read (`collective_ops(body)`)."""
    computations = dict(_HLO_COMPUTATION_RE.findall(text))

    def with_callees(name: str, seen: set) -> str:
        if name in seen or name not in computations:
            return ""
        seen.add(name)
        body = computations[name]
        return body + "".join(
            with_callees(c, seen) for c in _HLO_CALLEE_RE.findall(body)
        )

    return [
        with_callees(re.search(r"body=%?([\w.\-]+)", line)[1], set())
        for line in text.splitlines()
        if _HLO_WHILE_RE.search(line) and scope in line
    ]


@dataclasses.dataclass(frozen=True)
class ReductionHost:
    """A compute fusion that carries steps of an asynchronous collective:
    ``name`` is the instruction (``fusion.668``), ``host_scope`` what the
    compute it calls is (see `reduction_schedule`)."""

    name: str
    host_scope: str


@dataclasses.dataclass(frozen=True)
class ScheduledReduction:
    """One cross-chip collective of a compiled program, how it runs, whose
    it is, and which instructions carry it. ``dtype`` and ``shape`` are its
    first result's (a combined all-reduce is tuple-shaped), ``nbytes``
    every result's. ``scope`` is the scope path of the collective's own
    ``op_name``. ``start`` is the instruction that issues it and ``done``
    the one during which the chip waits for it: the two fusions of an
    asynchronous collective (``async-collective-start.N`` / ``-done.N``),
    a plain ``-start`` / ``-done`` pair, or, twice, the one instruction of
    a synchronous collective. ``hosts`` are the compute fusions between
    the two that carry its steps. The names are what a profiler's device
    events are called, so a trace is joined to this table by name."""

    kind: str             # "all-reduce" | "reduce-scatter" | "all-gather"
    dtype: str
    shape: tuple
    nbytes: int
    asynchronous: bool
    channel: int | None = None
    scope: str = ""
    start: str = ""
    done: str = ""
    hosts: tuple = ()     # of ReductionHost

    @property
    def reduces(self) -> bool:
        """Whether it sums (an all-gather moves bytes and adds nothing)."""
        return self.kind != "all-gather"


_HLO_REDUCTION_RE = re.compile(
    r"=\s*(\([^=]*?\)|\S+)\s+(all-reduce|reduce-scatter|all-gather)"
    r"(-start)?\("
)
_HLO_DONE_RE = re.compile(
    r"(?:all-reduce|reduce-scatter|all-gather)-done\((?:.*?[ (])?%?([\w.\-]+)\)"
)
_HLO_INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_HLO_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_HLO_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# XLA:TPU runs an asynchronous collective as fusions of the entry
# computation: `async-collective-start`, steps that ride inside compute
# fusions (computations named `async_collective_fusion.N`), and
# `async-collective-done`. Each of their computations restates the
# collective under its one channel id.
_ASYNC_FUSION = "async_collective_fusion"
_ASYNC_START, _ASYNC_DONE = "async-collective-start", "async-collective-done"
# What the program itself names (`jax.named_scope("hvt.optimizer")`, ...).
PROGRAM_SCOPE = "hvt."


def scope_path(op_name: str) -> str:
    """The named scopes of an instruction's ``op_name``: what lies between
    the leading ``jit(<fn>)`` and the trailing primitive.
    ``jit(train_step)/hvt.optimizer/add`` -> ``hvt.optimizer``;
    ``jit(train_step)/add`` -> nothing."""
    parts = op_name.split("/")
    if re.fullmatch(r"p?jit\(.*\)", parts[0]):
        parts = parts[1:]
    return "/".join(parts[:-1])


def _host_scope(body: str) -> str:
    """What the compute of a host fusion is, from the ``op_name``s of its
    computation's instructions other than the collective: the scope path
    most of them share, among those that hold a program scope where any
    does (an AdamW pass also holds converts that name nothing), and never
    simply the first instruction's (often a convert or a broadcast under
    no scope)."""
    paths = [
        scope_path(op_name)
        for line in body.splitlines() if not _HLO_REDUCTION_RE.search(line)
        for op_name in _HLO_OP_NAME_RE.findall(line)
    ]
    paths = [p for p in paths if p]
    named = [p for p in paths if PROGRAM_SCOPE in p]
    counts: dict = {}
    for path in named or paths:
        counts[path] = counts.get(path, 0) + 1
    return max(counts, key=counts.get) if counts else ""


def reduction_schedule(text: str) -> list[ScheduledReduction]:
    """Compiled HLO: every non-scalar all-reduce, reduce-scatter and
    all-gather, once, told apart by how the compiler scheduled it, with
    the instructions that carry it (`ScheduledReduction`). Asynchronous: a
    ``-start`` / ``-done`` pair, or a collective inside the fusions of an
    asynchronous collective (the scheduler may then put compute between
    its start and its done). Synchronous: the plain instruction, during
    which the chip's compute waits. (Scalars are the metric means, as in
    `gradient_reductions`.) A text without metadata gives empty scopes."""
    computations = _HLO_COMPUTATION_RE.findall(text)
    callers = {}  # computation -> the fusion instruction that calls it
    dones = {}    # `-start` instruction -> its `-done`
    for _, body in computations:
        for line in body.splitlines():
            name = _HLO_INSTRUCTION_RE.match(line)
            if name and (calls := _HLO_CALLS_RE.search(line)):
                callers[calls.group(1)] = name.group(1)
            if name and (done := _HLO_DONE_RE.search(line)):
                dones[done.group(1)] = name.group(1)
    rows, by_channel = [], {}
    for computation, body in computations:
        caller = callers.get(computation, "")
        fused = computation.startswith(_ASYNC_FUSION) or caller.startswith(
            (_ASYNC_START, _ASYNC_DONE))
        for line in body.splitlines():
            m = _HLO_REDUCTION_RE.search(line)
            if not m:
                continue
            results = [
                (_canon_dtype(dtype),
                 tuple(int(d) for d in dims.split(",") if d))
                for dtype, dims in _HLO_TYPE_RE.findall(m.group(1))
            ]
            if m.group(2) == "all-gather" and m.group(3):
                # `all-gather-start` gives (operands, results).
                results = results[len(results) // 2:]
            if not any(shape for _, shape in results):
                continue
            channel = _HLO_CHANNEL_RE.search(line)
            channel = int(channel.group(1)) if channel else None
            op_name = _HLO_OP_NAME_RE.search(line)
            row = by_channel.get(channel) if fused else None
            if row is None:
                row = {
                    "kind": m.group(2), "dtype": results[0][0],
                    "shape": results[0][1],
                    "nbytes": sum(_nbytes(*result) for result in results),
                    "asynchronous": fused or bool(m.group(3)),
                    "channel": channel, "scope": "", "start": "", "done": "",
                    "hosts": [],
                }
                rows.append(row)
                if fused and channel is not None:
                    by_channel[channel] = row
            if op_name and not row["scope"]:
                row["scope"] = scope_path(op_name.group(1))
            if not fused:
                own = _HLO_INSTRUCTION_RE.match(line).group(1)
                row["start"] = own
                row["done"] = dones.get(own, own) if m.group(3) else own
            elif caller.startswith(_ASYNC_START):
                row["start"] = caller
            elif caller.startswith(_ASYNC_DONE):
                row["done"] = caller
            elif caller:
                row["hosts"].append(ReductionHost(caller, _host_scope(body)))
    return [
        ScheduledReduction(**{**row, "hosts": tuple(row["hosts"])})
        for row in rows
    ]


def asynchronous_share(reductions) -> float | None:
    """Bytes of the asynchronous reductions over the bytes of all of
    them; None for a program that reduces nothing across chips."""
    reductions = [r for r in reductions if r.reduces]
    total = sum(r.nbytes for r in reductions)
    if not total:
        return None
    return sum(r.nbytes for r in reductions if r.asynchronous) / total


# Donation: lowered StableHLO marks donated args with `tf.aliasing_output`
# / `jax.buffer_donor` arg attributes; compiled HLO records the aliasing
# map in the module header.
_STABLEHLO_DONOR_RE = re.compile(
    r"tf\.aliasing_output\s*=\s*(\d+)|jax\.buffer_donor\s*=\s*true"
)
_HLO_ALIAS_RE = re.compile(r"\{[0-9, ]*\}:\s*\((\d+)\s*,")


def donated_args(text: str) -> list[int]:
    """Argument numbers the program donates (aliases to outputs).

    From compiled HLO the numbers are the header's ``input_output_alias``
    parameter indices; from lowered StableHLO, the positions of
    arg-attribute donation markers in declaration order (an approximation
    — compile for the exact map)."""
    if "input_output_alias=" in text:
        header = text.split("input_output_alias={", 1)[1]
        # the alias map is brace-balanced; entries look like
        # `{0}: (0, {}, may-alias)` — harvest the arg numbers.
        depth, end = 1, 0
        for i, ch in enumerate(header):
            if ch == "{":
                depth += 1
            elif ch == "}":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        return sorted({
            int(g) for g in _HLO_ALIAS_RE.findall(header[:end])
        })
    hits = []
    for i, m in enumerate(_STABLEHLO_DONOR_RE.finditer(text)):
        hits.append(int(m.group(1)) if m.group(1) is not None else i)
    return sorted(set(hits))


# --- expectations -----------------------------------------------------------


class ProgramAuditError(AssertionError):
    """A compiled program violated its expectations (structured diff in
    the message)."""


@dataclasses.dataclass
class ProgramExpectation:
    """What a compiled step must look like. Unset fields are unchecked.

    ``wire`` implies at least one gradient reduction exists (an empty
    program trivially satisfying 'every reduction is int8' is itself a
    violation — the invariant is about traffic that must be present)."""

    gradient_reductions: int | None = None   # exact count
    max_gradient_reductions: int | None = None
    # Compression name or dtype. Check the LOWERED StableHLO: post-
    # optimization HLO may legalize wire dtypes per backend (CPU upcasts
    # the bf16 all-reduce to f32) — counts survive optimization, element
    # types do not.
    wire: str | None = None
    no_explicit_collectives: bool = False
    min_donated: int | None = None
    # Scatter mode (the ZeRO-1 composed step): the gradient traffic must
    # be ONE bucketed reduce-scatter group — only scatter-form reductions
    # (`scatter_reductions`), with NO full-payload (non-scalar)
    # all-reduce anywhere in the program. ``scatter_reductions`` pins the
    # exact op count (== the bucket count); the bare flag only asserts
    # the shape. Like ``wire``, check the LOWERED StableHLO — it carries
    # the explicit collectives only, so the sharded update's implicit
    # parameter all-gather cannot leak into the counts.
    scatter_mode: bool = False
    scatter_reductions: int | None = None
    # The EP dispatch/combine shape: exactly N PAYLOAD (rank >= 2)
    # all-to-alls — `collectives.all_to_all` submissions; rank-1
    # scale/column all-to-alls never count (`payload_alltoalls`).
    alltoalls: int | None = None

    @classmethod
    def parse(cls, spec: str) -> "ProgramExpectation":
        """CLI grammar: comma-separated tokens —
        ``one-reduction`` | ``reductions=N`` | ``max-reductions=N`` |
        ``wire=int8`` | ``no-collectives`` | ``donates=N`` |
        ``scatter-reduction`` | ``scatters=N`` | ``alltoalls=N``.
        (``overlap`` is a CLI-level expectation: it needs two compiles.)
        """
        exp = cls()
        for token in spec.split(","):
            token = token.strip().lower()
            if not token:
                continue
            key, _, value = token.partition("=")
            if token == "one-reduction":
                exp.gradient_reductions = 1
            elif key == "reductions" and value:
                exp.gradient_reductions = int(value)
            elif key == "max-reductions" and value:
                exp.max_gradient_reductions = int(value)
            elif key == "wire" and value:
                wire_dtype(value)  # validate now -> usage error, not audit
                exp.wire = value
            elif token == "no-collectives":
                exp.no_explicit_collectives = True
            elif key == "donates" and value:
                exp.min_donated = int(value)
            elif token == "scatter-reduction":
                exp.scatter_mode = True
            elif key == "scatters" and value:
                exp.scatter_mode = True
                exp.scatter_reductions = int(value)
            elif key == "alltoalls" and value:
                exp.alltoalls = int(value)
            else:
                raise ValueError(
                    f"unknown expectation {token!r} — grammar: "
                    "one-reduction | reductions=N | max-reductions=N | "
                    "wire=<int8|fp8|bf16|fp16|f32> | no-collectives | "
                    "donates=N | scatter-reduction | scatters=N | "
                    "alltoalls=N | overlap"
                )
        return exp


def audit(text: str, expects: ProgramExpectation, *,
          ops: list | None = None) -> list[str]:
    """Check `text` against `expects`; returns human-readable violation
    lines (empty = clean). ``ops`` lets a caller that already parsed
    the program (`collective_ops`) skip the re-parse; the text is still
    needed for the donation-alias header."""
    if ops is None:
        ops = collective_ops(text)
    grads = gradient_reductions(ops)
    violations = []
    if expects.no_explicit_collectives and ops:
        violations.append(
            f"expected NO explicit collectives, found {len(ops)}:\n"
            + _op_table(ops)
        )
    if expects.gradient_reductions is not None and len(grads) != (
        expects.gradient_reductions
    ):
        violations.append(
            f"expected exactly {expects.gradient_reductions} gradient "
            f"reduction(s) per step, found {len(grads)}:\n"
            + _op_table(grads)
        )
    if expects.max_gradient_reductions is not None and len(grads) > (
        expects.max_gradient_reductions
    ):
        violations.append(
            f"expected at most {expects.max_gradient_reductions} gradient "
            f"reduction(s), found {len(grads)}:\n" + _op_table(grads)
        )
    if expects.scatter_mode:
        scatters = scatter_reductions(ops)
        full_ar = [
            op for op in ops if op.kind == "all-reduce" and not op.scalar
        ]
        if full_ar:
            violations.append(
                "scatter mode forbids full-payload all-reduces (the "
                "reduction must lower into the sharded update's layout), "
                f"found {len(full_ar)}:\n" + _op_table(full_ar)
            )
        if not scatters:
            violations.append(
                "expected scatter-form gradient reductions (reduce-"
                "scatter / payload all-to-all), found none"
            )
        if expects.scatter_reductions is not None and len(scatters) != (
            expects.scatter_reductions
        ):
            violations.append(
                f"expected exactly {expects.scatter_reductions} scatter-"
                f"form reduction(s) — one bucketed group — found "
                f"{len(scatters)}:\n" + _op_table(scatters)
            )
    if expects.wire is not None:
        want = wire_dtype(expects.wire)
        payload = _wire_payload_ops(ops)
        if not payload:
            violations.append(
                f"expected {expects.wire} ({want}) gradient traffic, "
                "found NO gradient reductions at all"
            )
        off_wire = [op for op in payload if op.dtype != want]
        if off_wire:
            violations.append(
                f"expected every gradient payload (reductions and "
                f"scatter all-to-alls) in {expects.wire} ({want}), found "
                "off-wire traffic:\n" + _op_table(off_wire)
            )
    if expects.alltoalls is not None:
        a2a = payload_alltoalls(ops)
        if len(a2a) != expects.alltoalls:
            excluded = [
                op for op in ops
                if op.kind == "all-to-all" and op.rank < 2
            ]
            violations.append(
                f"expected exactly {expects.alltoalls} payload "
                f"all-to-all(s) (the dispatch/combine shape), found "
                f"{len(a2a)}:\n" + _op_table(a2a)
                + (
                    f"\n      ({len(excluded)} rank-1 scale/column "
                    "all-to-all(s) excluded from the count)"
                    if excluded else ""
                )
            )
    if expects.min_donated is not None:
        donated = donated_args(text)
        if len(donated) < expects.min_donated:
            violations.append(
                f"expected >= {expects.min_donated} donated (aliased) "
                f"inputs, found {len(donated)}: {donated}"
            )
    if violations:
        totals = op_bytes_by_kind(ops)
        if totals:
            # Expectation-diff context: where the wire bytes actually
            # went, per kind — the first question a failed count raises.
            violations.append(
                "payload op_bytes by kind: " + ", ".join(
                    f"{k}={v}" for k, v in sorted(totals.items())
                )
            )
    return violations


def _op_table(ops) -> str:
    if not ops:
        return "      (none)"
    return "\n".join("      " + op.describe() for op in ops)


def assert_program(text: str, expects: ProgramExpectation | str) -> None:
    """Raise `ProgramAuditError` (an AssertionError) with a structured
    diff when `text` violates `expects` (a `ProgramExpectation` or the
    CLI expectation string)."""
    if isinstance(expects, str):
        expects = ProgramExpectation.parse(expects)
    violations = audit(text, expects)
    if violations:
        grads = gradient_reductions(text)
        raise ProgramAuditError(
            "compiled program violates expectations:\n"
            + "\n".join(f"  - {v}" for v in violations)
            + f"\n  gradient reductions observed: {len(grads)}"
            + (("\n" + _op_table(grads)) if grads else "")
        )
