"""Device time by plan step: the program's map from its compiled ops
back to the steps of its plan.

Every step is traced under one named scope that says what the code
decided for it (:func:`step_scope_name`:
``tnc.step.<NNNN>.<size>.<mode>.<form>``, with the sub-scopes ``prep``,
``dot`` and ``out``), and the non-step work of a slice under
``tnc.slice.index``, ``tnc.slice.sum`` and ``tnc.chunk.io``
(:mod:`tnc_tpu.ops.split_complex`, :mod:`tnc_tpu.ops.sliced`,
:mod:`tnc_tpu.ops.chunked`). XLA keeps that scope as the ``op_name`` of
every instruction of the OPTIMIZED program, fusions and layout copies
included, so the text of a compiled program says which step each of its
ops came from — and a profiler's trace names device ops by exactly
those instruction names (``jit_tnc_residual_c00/fusion.903``).

Three parts:

- **at trace time** :func:`tnc_tpu.ops.backends.named_jit` registers
  each program by its role name (:func:`register`) and, inside the
  traced Python function — so only when JAX traces, never per call —
  records the abstract arguments (:func:`tracing`); a step notes the
  scope it was traced under (:func:`note_step`). With no one asking,
  that is a few strings a trace.
- **on request** :func:`device_op_table` lowers and compiles every
  registered program again on its recorded arguments (the persistent
  compile cache or JAX's own in-memory caches answer where they can),
  parses the optimized text (:func:`parse_hlo_ops`: plain text
  handling, no JAX) and checks it against the scopes the trace noted: a
  text with no ``tnc.step.`` scope, or with other scopes than the
  program's own, came from a compile-cache entry written by older code
  (JAX leaves debug info out of the cache key) and is ``stale``.
- :func:`step_seconds` joins a list of ``(module/op, seconds)`` — what
  a trace reduction keeps of a window — with the table: seconds by
  step, by part, by form, by mode, ``mixed`` and ``unattributed``.

See ``docs/observability.md`` ("Device time by plan step").
"""

from __future__ import annotations

import math
import re
import threading
import time
import weakref
from typing import Any, Iterable, Sequence

STEP_SCOPE = "tnc.step."
#: sub-scopes of a step: the planned transposes and staged ops of the
#: operands; the dot (or gauss's three dots and their sums); the
#: result's way to its stored or carried shape
STEP_PARTS = ("prep", "dot", "out")
#: the non-step work of a slice: cutting the sliced leaves for a slice;
#: the (Kahan) sum over slices; stacking and unstacking at chunk
#: boundaries. The table's ``part`` of such an op is the scope less
#: ``tnc.``
SLICE_INDEX = "tnc.slice.index"
SLICE_SUM = "tnc.slice.sum"
CHUNK_IO = "tnc.chunk.io"
NON_STEP_SCOPES = (SLICE_INDEX, SLICE_SUM, CHUNK_IO)

#: programs remembered per role name: each keeps its jitted callable
#: (weakly: an evicted program is forgotten with its cache entry)
_MAX_PER_NAME = 16


def step_scope_name(number: int, size: str, mode: str, form: str) -> str:
    """The named scope of one step: its index in the step list of the
    program being traced, ``large`` | ``small``
    (:func:`tnc_tpu.ops.program.step_size_class`), the lowering that
    ran after every fallback, and where the streamed operand's prep
    ended (``tiled`` | ``staged`` | ``matrix``).

    >>> step_scope_name(3, "large", "block", "tiled")
    'tnc.step.0003.large.block.tiled'
    """
    return f"{STEP_SCOPE}{number:04d}.{size}.{mode}.{form}"


# -- trace time: what named_jit and the steps leave behind ----------------


class ModuleRecord:
    """What is needed to ask one jitted program for its compiled text
    later: the callable (weakly), the abstract arguments of each trace,
    the steps it was built from and the scope each was traced under."""

    def __init__(self, name: str, steps: Sequence | None, sharding):
        self.name = name
        self.sharding = sharding
        #: per step of the module, in the order of its numbers:
        #: ``(PairStep, "row" | "once", index in the plan handed out)``
        self.steps = tuple(steps) if steps is not None else None
        self.jitted: Any = None  # weakref.ref once named_jit has it
        self.variants: list[tuple] = []  # (args, kwargs) of each trace
        self.scopes: dict[int, str] = {}  # step number -> its scope

    def note_trace(self, args, kwargs) -> None:
        import jax

        def abstract(x):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=self.sharding,
                weak_type=bool(getattr(x, "weak_type", False)),
            )

        variant = jax.tree.map(abstract, (tuple(args), dict(kwargs)))
        if not any(str(variant) == str(v) for v in self.variants):
            self.variants.append(variant)


_RECORDS: dict[str, list[ModuleRecord]] = {}
_LOCK = threading.Lock()
_ACTIVE = threading.local()  # .stack: the records being traced, innermost last


def register(name: str, steps: Sequence | None = None, sharding=None) -> ModuleRecord:
    """A new record for a program jitted under ``name`` (without the
    ``jit_``). ``steps`` is its step list as ``(step, runs, plan
    index)`` triples, ``runs`` ``"row"`` (once a slice) or ``"once"``
    (once a dispatch); ``sharding`` is given to every abstract argument
    when the text is asked for (the SPMD program's replicated leaves)."""
    record = ModuleRecord(name, steps, sharding)
    with _LOCK:
        kept = _RECORDS.setdefault(name, [])
        kept[:] = [r for r in kept if r.jitted is None or r.jitted() is not None]
        kept.append(record)
        del kept[:-_MAX_PER_NAME]
    return record


class tracing:
    """Context of the traced Python function of one registered program:
    records the abstract arguments, and is where :func:`note_step`
    finds the program a step belongs to."""

    def __init__(self, record: ModuleRecord, args, kwargs):
        record.note_trace(args, kwargs)
        self.record = record

    def __enter__(self):
        stack = getattr(_ACTIVE, "stack", None)
        if stack is None:
            stack = _ACTIVE.stack = []
        stack.append(self.record)
        return self.record

    def __exit__(self, *exc):
        _ACTIVE.stack.pop()
        return False


def note_step(number: int, scope: str) -> None:
    """The scope step ``number`` of the program being traced ran under
    (nothing outside a registered program's trace)."""
    stack = getattr(_ACTIVE, "stack", None)
    if stack:
        stack[-1].scopes[number] = scope


def registered() -> dict[str, list[ModuleRecord]]:
    with _LOCK:
        return {name: list(records) for name, records in _RECORDS.items()}


# -- the parser: optimized HLO text -> op -> scopes -----------------------

_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)"
)
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_WRAPPER = re.compile(r"^\w+\((.*)\)$")  # vmap(...), jvp(...), jit(...)
# computations an instruction RUNS as ops of their own (a trace shows
# their instructions), against those it holds inline (a fusion's, a
# reduce's, an asynchronous wrapper's)
_RUNS = ("body", "condition", "true_computation", "false_computation")
# instructions of a fused computation that do no work of their own: a
# scope they carry (a bitcast inherits the reshape's) says nothing
_FREE = frozenset(
    ("parameter", "constant", "bitcast", "get-tuple-element", "tuple", "iota")
)
_DOTS = frozenset(("dot", "convolution"))
# data movement the compiler may add with no metadata of its own
_MOVES = frozenset((
    "copy", "copy-start", "copy-done", "slice-start", "slice-done",
    "async-start", "async-done", "bitcast", "custom-call",
))


def _opcode(rest: str) -> tuple[str, list[str]]:
    """``f32[8,64]{1,0:T(8,128)} fusion(%p.1, %q), kind=...`` ->
    ``("fusion", ["p.1", "q"])``: the opcode after the (possibly nested)
    type, and the operands' names."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            opcode, _, tail = rest[i + 1:].partition("(")
            depth = 1
            for j, ch in enumerate(tail):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    tail = tail[:j]
                    break
            return opcode.strip(), re.findall(r"%([\w.\-]+)", tail)
    return "", []


def parse_scope(op_name: str):
    """``(scope, part)`` of an ``op_name``: the innermost path element
    that is one of the program's scopes — ``while``/``body``,
    ``vmap(...)``, ``jit(...)`` and ``shard_map`` wrappers skipped over;
    a step inside the ``tnc.chunk.io`` of the loop that runs it is the
    step's — and, for a step, the sub-scope after it. ``(None, None)``
    without one.

    >>> parse_scope("jit(tnc_residual_c00)/while/body/closed_call/"
    ...             "vmap(tnc.step.0003.large.block.tiled)/dot/dot_general")
    ('tnc.step.0003.large.block.tiled', 'dot')
    >>> parse_scope("jit(f)/tnc.chunk.io/while/body/tnc.slice.index/gather")
    ('tnc.slice.index', 'slice.index')
    >>> parse_scope("jit(f)/tnc.small/transpose")
    (None, None)
    """

    def bare(element: str) -> str:
        while True:
            m = _WRAPPER.match(element)
            if m is None:
                return element
            element = m.group(1)

    elements = [bare(e) for e in op_name.split("/")]
    for i in range(len(elements) - 1, -1, -1):
        element = elements[i]
        if element.startswith(STEP_SCOPE):
            part = elements[i + 1] if i + 1 < len(elements) else None
            return element, part if part in STEP_PARTS else None
        if element in NON_STEP_SCOPES:
            return element, element[len("tnc."):]
    return None, None


def _step_number(scope: str) -> int | None:
    if not scope.startswith(STEP_SCOPE):
        return None
    try:
        return int(scope[len(STEP_SCOPE):].split(".", 1)[0])
    except ValueError:
        return None


def _runs_as_ops(kind: str, opcode: str) -> bool:
    """Does an instruction run the computation it names by ``kind`` as
    ops of their own (a loop's body, a branch, a ``call``'s target), or
    hold it inline (a fusion's, a reduce's, an asynchronous wrapper's)?"""
    return kind in _RUNS or (kind == "to_apply" and opcode == "call")


def _owners(work: list[tuple]) -> tuple[list[str], str | None]:
    """``(owners, part)`` of an op from the scoped working instructions
    it holds, ``(scope, part, opcode, is_root)`` each (itself, for a
    plain instruction). Where it holds a ``dot`` or ``convolution`` the
    dots' steps own it, whatever its root, and its part is the dots'
    own sub-scope: ``dot`` for a step's contraction, ``prep`` for the
    one-hot matmul of a staged prep's lane permutation (``lanemix``).
    Else every step with work in it owns it, and a non-step scope only
    where no step does (cutting a leaf or a row out of a stack inside a
    step's transpose is an offset, not a pass); its part is its root's
    sub-scope, else that of the last of the owners' instructions."""
    dots = [w for w in work if w[2] in _DOTS and w[0].startswith(STEP_SCOPE)]
    if dots:
        parts = {w[1] for w in dots}
        return sorted({w[0] for w in dots}), "dot" if "dot" in parts else dots[0][1]
    steps = [w for w in work if w[0].startswith(STEP_SCOPE)]
    work = steps or work
    if not work:
        return [], None
    roots = [w[1] for w in work if w[3]]
    return sorted({w[0] for w in work}), roots[0] if roots else work[-1][1]


def parse_hlo_ops(text: str) -> dict:
    """``{"module": name, "scopes": {step scopes seen}, "ops": {op:
    entry}}`` of one optimized HLO module's text. An op is an
    instruction of the entry computation or of a computation that runs
    as ops of its own (a ``while``'s body and condition, a
    conditional's branches, a ``call``'s target). Its entry:

    - ``owners``: the scopes it does work for — its own ``op_name``'s,
      or, where it calls a computation inline (a fusion, an
      asynchronous wrapper), those of that computation's working
      instructions (:func:`_owners`); more than one is ``mixed``;
      ``steps``: the numbers of the steps among them;
    - ``part``: ``prep`` | ``dot`` | ``out``: for a fusion that holds a
      ``dot`` or ``convolution`` the sub-scope of that instruction
      whatever its root (``dot``: the step's contraction; ``prep``: a
      staged prep's ``lanemix`` matmul); a non-step scope's name less
      ``tnc.``; ``None`` outside every scope;
    - ``opcode``.

    A move the compiler added with no metadata (a ``copy``, a
    ``copy-start``/``copy-done`` or ``slice-start``/``slice-done`` pair
    that prefetches an operand into fast memory) goes to the one owner
    of the ops that read its result, part ``prep``.
    """
    module = ""
    computations: dict[str, list[tuple]] = {}
    entry = None
    current: list | None = None
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        if current is None:
            head = _HEAD.match(line)
            if head and line.rstrip().endswith("{"):
                current = computations.setdefault(head.group(2), [])
                if head.group(1):
                    entry = head.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        rest = m.group(3)
        op_name = _OP_NAME.search(rest)
        called = _CALLED.findall(rest)
        branches = _BRANCHES.search(rest)
        if branches:  # a conditional's branches run as a true_computation does
            called += [
                ("true_computation", b.strip().lstrip("%"))
                for b in branches.group(1).split(",") if b.strip()
            ]
        opcode, operands = _opcode(rest)
        current.append((
            m.group(2), opcode, bool(m.group(1)),
            parse_scope(op_name.group(1)) if op_name else (None, None),
            called, operands,
        ))

    def inline_work(comp: str, seen: set) -> list[tuple]:
        """``(scope, part, opcode, is_root)`` of the working
        instructions a computation holds, nested ones included."""
        if comp in seen or comp not in computations:
            return []
        seen.add(comp)
        out = []
        for _, opcode, is_root, (scope, part), called, _ in computations[comp]:
            if opcode not in _FREE:
                out.append((scope, part, opcode, is_root))
            for _, target in called:
                out.extend(
                    (s, p, o, False) for s, p, o, _ in inline_work(target, seen)
                )
        return out

    # the computations whose instructions run as ops of their own
    runs, queue = set(), [entry] if entry else list(computations)[-1:]
    while queue:
        comp = queue.pop()
        if comp in runs or comp not in computations:
            continue
        runs.add(comp)
        for _, opcode, _, _, called, _ in computations[comp]:
            queue.extend(
                target for kind, target in called if _runs_as_ops(kind, opcode)
            )

    ops: dict[str, dict] = {}
    users: dict[str, list[str]] = {}
    first_operand: dict[str, str] = {}
    for comp in runs:
        for name, opcode, _, own, called, operands in computations[comp]:
            first_operand[name] = operands[0] if operands else ""
            for operand in operands:
                users.setdefault(operand, []).append(name)
            work = [
                w
                for kind, target in called
                if not _runs_as_ops(kind, opcode)
                for w in inline_work(target, set())
                if w[0] is not None
            ] or ([own + (opcode, True)] if own[0] is not None else [])
            owners, part = _owners(work)
            ops[name] = {
                "steps": sorted(
                    n for n in map(_step_number, owners) if n is not None
                ),
                "owners": owners,
                "part": part,
                "opcode": opcode,
            }
    # a move the compiler put in by itself (a prefetch into fast memory,
    # a layout copy: no metadata) is work for the step that reads it
    def reader(name: str, seen: frozenset) -> dict | None:
        found = None
        for user in users.get(name, ()):
            entry = ops.get(user)
            if entry is None or user in seen:
                return None
            if not entry["owners"] and entry["opcode"] in _MOVES:
                entry = reader(user, seen | {user})
            if entry is None or not entry["owners"]:
                return None
            if found is not None and found["owners"] != entry["owners"]:
                return None
            found = entry
        return found

    for name, entry in ops.items():
        if not entry["owners"] and entry["opcode"] in _MOVES:
            read_by = reader(name, frozenset((name,)))
            if read_by is not None:
                entry.update(
                    steps=read_by["steps"], owners=read_by["owners"],
                    part="prep" if read_by["steps"] else read_by["part"],
                )
    # the second half of an asynchronous pair does its first half's work
    for name, entry in ops.items():
        first = ops.get(first_operand[name])
        if not entry["owners"] and entry["opcode"].endswith("-done") and first:
            entry.update(
                steps=first["steps"], owners=first["owners"], part=first["part"]
            )
    scopes = {
        scope
        for instrs in computations.values()
        for _, _, _, (scope, _), _, _ in instrs
        if scope is not None and scope.startswith(STEP_SCOPE)
    }
    return {"module": module, "scopes": scopes, "ops": ops}


# -- on request: the table ------------------------------------------------


def step_facts(record: ModuleRecord) -> list[dict]:
    """Per step of a registered program the static facts the program
    already computes: the scope it was traced under, split into
    ``size`` / ``mode`` / ``form``; the elements it streams a run
    (larger operand in + result out) and its larger operand's alone
    (``operand``); its multiply-adds and ``k``;
    ``runs`` (``row``: once a slice; ``once``: once a dispatch); its
    index in the plan that was handed out."""
    from tnc_tpu.ops.program import step_dims, step_flops, step_streamed_elems

    facts = []
    for number, (step, runs, plan_index) in enumerate(record.steps or ()):
        scope = record.scopes.get(number)
        size = mode = form = None
        if scope is not None:
            size, mode, form = scope[len(STEP_SCOPE):].split(".")[1:4]
        facts.append({
            "number": number, "scope": scope, "size": size, "mode": mode,
            "form": form, "elements": step_streamed_elems(step),
            "operand": max(math.prod(step.a_view), math.prod(step.b_view)),
            "macs": step_flops(step), "k": step_dims(step)[1],
            "runs": runs, "plan_index": plan_index,
        })
    return facts


def module_table(record: ModuleRecord, text: str) -> dict:
    """One variant of the table from a program's record and the
    optimized text of its executable: ``status`` ``ok``, or ``stale``
    with ``why`` — the text holds no step scope, or a scope the trace
    of this program did not note (another naming, another lowering: an
    executable compiled by older code and loaded from the persistent
    cache, which is keyed without debug info)."""
    parsed = parse_hlo_ops(text)
    status, why = "ok", ""
    noted = set(record.scopes.values())
    if record.steps and not parsed["scopes"]:
        status, why = "stale", "the compiled text holds no tnc.step. scope"
    elif not parsed["scopes"] <= noted:
        other = sorted(parsed["scopes"] - noted)
        status = "stale"
        why = f"{len(other)} scopes of the text are not the program's own, first {other[0]}"
    return {
        "status": status, "why": why, "module": parsed["module"],
        "ops": parsed["ops"], "steps": step_facts(record),
    }


def device_op_table(names: Iterable[str] | None = None) -> dict:
    """``{module: [variant, ...]}`` for every registered program that
    was traced in this process (``module`` as a trace names it:
    ``jit_tnc_residual_c00``). A variant is one traced signature of one
    program: ``status`` / ``why``, ``ops`` (``{op name: {"steps",
    "owners", "part", "opcode"}}``, see :func:`parse_hlo_ops`),
    ``steps`` (:func:`step_facts`) and ``seconds``, what lowering and
    compiling it again cost. Asked for after a traced window, never
    inside one: every variant is lowered and compiled here."""
    wanted = set(names) if names is not None else None
    table: dict[str, list[dict]] = {}
    for name, records in registered().items():
        if wanted is not None and name not in wanted and f"jit_{name}" not in wanted:
            continue
        for record in records:
            jitted = record.jitted() if record.jitted is not None else None
            if jitted is None:
                continue
            for args, kwargs in list(record.variants):
                t0 = time.monotonic()
                text = jitted.lower(*args, **kwargs).compile().as_text()
                variant = module_table(record, text)
                variant["seconds"] = time.monotonic() - t0
                table.setdefault(variant["module"] or f"jit_{name}", []).append(variant)
    return table


# -- the join -------------------------------------------------------------


def step_seconds(device_ops: Iterable, table: dict | None = None) -> dict | None:
    """Join ``device_ops`` — ``(module/op, seconds)`` pairs, as a trace
    reduction keeps them — with the op table (default:
    :func:`device_op_table` of the modules named). ``None`` where the
    table knows none of the modules, or a module of the list is
    ``stale``: a stale table is not believed. Else seconds

    - ``total_s`` = ``attributed_s`` (ops with exactly one owner: one
      step or one non-step scope) + ``mixed_s`` (several owners)
      + ``unattributed_s`` (no owner, an op the table lacks, a module it
      lacks);
    - ``by_part`` / ``by_form`` / ``by_mode``: the attributed seconds
      by ``prep`` | ``dot`` | ``out`` | ``slice.index`` | ``slice.sum``
      | ``chunk.io``, and those of steps by form and by mode;
      ``by_opcode``: each part's by the op's opcode (a ``copy`` under
      ``dot`` is a relayout the dot's own scope holds);
    - ``steps``: per ``(module, number)`` its facts, ``seconds`` (its
      own attributed ops), ``mixed_s`` (an equal share of each mixed op
      it owns a part of) and ``by_part``;
    - ``unknown_ops``: ops of a known module that are no key of its
      table (none, if the text asked for is the text that ran);
    - ``unattributed``: the ten longest ops without an owner.

    Of several variants of a module the one that knows the most of the
    module's seconds is read, and of two that know as much the one with
    the fewest fusions the window did not run."""
    device_ops = [(str(name), float(seconds)) for name, seconds in device_ops]
    by_module: dict[str, list[tuple[str, float]]] = {}
    for name, seconds in device_ops:
        module, _, op = name.partition("/")
        by_module.setdefault(module, []).append((op, seconds))
    if table is None:
        table = device_op_table(by_module)
    known = [m for m in by_module if table.get(m)]
    if not known:
        return None
    out: dict[str, Any] = {
        "total_s": sum(s for _, s in device_ops), "attributed_s": 0.0,
        "mixed_s": 0.0, "unattributed_s": 0.0, "by_part": {}, "by_form": {},
        "by_mode": {}, "by_opcode": {}, "steps": {}, "unknown_ops": [],
        "unattributed": [], "table_s": 0.0,
    }

    def add(where: dict, key, seconds: float) -> None:
        where[key] = where.get(key, 0.0) + seconds

    loose: list[tuple[str, float]] = []
    for module, ops in by_module.items():
        variants = table.get(module)
        if not variants:
            out["unattributed_s"] += sum(s for _, s in ops)
            loose.extend((f"{module}/{op}", s) for op, s in ops)
            continue
        if any(v["status"] != "ok" for v in variants):
            return None
        out["table_s"] += sum(v.get("seconds", 0.0) for v in variants)
        ran = {op for op, _ in ops}
        variant = max(
            variants,
            key=lambda v: (
                sum(s for op, s in ops if op in v["ops"]),
                # two signatures of one program share most op names:
                # the one that ran has its fusions in the window
                -sum(
                    1 for op, e in v["ops"].items()
                    if e["opcode"] == "fusion" and op not in ran
                ),
            ),
        )
        facts = {f["number"]: f for f in variant["steps"]}

        def step_row(number: int) -> dict:
            row = out["steps"].get((module, number))
            if row is None:
                row = out["steps"][(module, number)] = {
                    **facts.get(number, {"number": number}),
                    "module": module, "seconds": 0.0, "mixed_s": 0.0,
                    "by_part": {},
                }
            return row

        for op, seconds in ops:
            entry = variant["ops"].get(op)
            if entry is None:
                out["unknown_ops"].append(f"{module}/{op}")
            if entry is None or not entry["owners"]:
                out["unattributed_s"] += seconds
                loose.append((f"{module}/{op}", seconds))
                continue
            part = entry["part"] or "none"
            if len(entry["owners"]) > 1:
                out["mixed_s"] += seconds
                for number in entry["steps"]:
                    step_row(number)["mixed_s"] += seconds / len(entry["owners"])
                continue
            out["attributed_s"] += seconds
            add(out["by_part"], part, seconds)
            add(out["by_opcode"].setdefault(part, {}), entry["opcode"], seconds)
            if entry["steps"]:
                row = step_row(entry["steps"][0])
                row["seconds"] += seconds
                add(row["by_part"], part, seconds)
                add(out["by_form"], row.get("form") or "none", seconds)
                add(out["by_mode"], row.get("mode") or "none", seconds)
    loose.sort(key=lambda pair: -pair[1])
    out["unattributed"] = loose[:10]
    return out


def named_scope(name: str):
    """``jax.named_scope(name)``: the one place the program's scopes
    are entered, so a test can patch them all to no-ops."""
    import jax

    return jax.named_scope(name)
