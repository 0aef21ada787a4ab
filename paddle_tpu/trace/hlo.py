"""The kernels of a compiled step, read from its optimised HLO text.

``kernel_rows(text)`` reads what ``compiled.as_text()`` prints and gives
one row for every instruction that runs as a device op: the
instructions of the computations that are NOT fused computations (the
entry, a ``while``'s body and condition, a conditional's branches, a
``call``'s callee) whose opcode does work, which is every opcode but
``parameter``, ``tuple``, ``get-tuple-element``, ``bitcast``,
``constant`` and the like (``_NO_WORK``). A fusion's row is read from
the fused computation it calls, nested fusions walked too, every
instruction of which still carries the ``op_name`` of the Program op it
came from: so a row says which Program ops XLA fused into the kernel
(``scopes``), the products in it with their FLOPs (``dots``) and the
bytes it declares (``bytes_in``, ``bytes_out``).

A row (numbers, strings, tuples and one dict of counts; the kernel
ledger's, ``paddle_tpu.trace.kernels``, which says what each field is
for):

``name``        the HLO instruction's name, ``%`` taken off: what the
                profiler's device event carries, unique in a module.
``opcode``, ``fusion_kind`` (``kLoop`` / ``kOutput`` / ``kInput`` /
``kCustom``; None for what is no fusion), ``computation`` (the entry's
or the loop body's name), ``custom_call_target`` (None for what is no
custom call).
``operands``, ``results``: ``((dtype, shape), ...)``, tuples flattened.
``bytes_in``, ``bytes_out``: their declared sizes, but an operand that
                a fusion's body reads only through ``slice`` /
                ``dynamic-slice`` counts the slices, an operand that is
                only updated in place (operand 0 of a
                ``dynamic-update-slice``) counts nothing, and a result
                that is a ``dynamic-update-slice`` counts the update. An
                asynchronous pair books its operands to the ``-start``
                and its result to the ``-done``.
``dots``        ``((op_name, lhs shape, rhs shape, result shape,
                contracted size, flops), ...)`` for every ``dot`` and
                ``convolution`` in the body: ``flops = 2 x result
                elements x contracted size``. A ``dot`` contracts its
                ``lhs_contracting_dims``; a ``convolution`` (the TPU's
                HLO writes a product as one) the kernel's input
                features (``i`` of ``dim_labels``, per feature group as
                the kernel's shape states it) times its window.
``scopes``      ``{"<type>.<seq>": instructions}`` over the body: the
                FIRST component of an instruction's ``op_name`` that is
                a Program op's scope or a serving scope, bare or inside
                ``jvp(`` / ``transpose(jvp(`` (the rule a profile's
                readers book a device op by); ``""`` counts the
                instructions that carry none.
``nested``      the scopes that sit (also) in a fusion nested in the
                body.
``root_scope``  the scope of the instruction's own ``op_name``: what a
                profile books the kernel's whole time to.
``op_name``     the instruction's own ``op_name`` (None without
                metadata).
``passes``      the sorted subset of ``("bwd", "fwd", "second")`` among
                the body's ``op_name``s: ``rematted_computation/`` in
                the name is a recompute region's second forward,
                otherwise ``transpose(jvp(`` is the backward, otherwise
                the forward (``pass_of``).
``estimated_cycles``  XLA's own estimate where ``backend_config`` has
                one, else None.

Pure: no ``jax``, nothing of the rest of the package. The text is read
line by line (an unrolled step's is tens of MB) and no regex runs over
more than one line's head or one attribute.
"""

import re

_NO_WORK = frozenset((
    "parameter", "tuple", "get-tuple-element", "bitcast", "constant",
    "after-all", "partition-id", "replica-id", "opt-barrier"))
SERVING_SCOPES = ("kv.read", "kv.write", "attn", "mlp", "head", "sample")
_SCOPE = re.compile(
    r"^(?:transpose\()?(?:jvp\()?([A-Za-z_]\w*\.\d+|%s)\)*$"
    % "|".join(re.escape(s) for s in SERVING_SCOPES))
_BYTES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2,
          "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
          "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16, "token": 0}
_LEAF = re.compile(r"([a-z]\w*)\[([^\]]*)\]")
_HEAD = re.compile(r"^\s+(ROOT )?%?([^\s=]+) = ")
_COMPUTATION = re.compile(r"^(ENTRY )?%?([^\s(]+) \(")
_NAME = re.compile(r"%([^\s,(){}]+)")
_CYCLES = re.compile(r'"estimated_cycles":"?(\d+)')
_CALLEES = {"while": ("condition=", "body="),
            "conditional": ("branch_computations=", "true_computation=",
                            "false_computation="),
            "call": ("to_apply=",), "async-start": ("calls=",)}
_SLICES = ("slice", "dynamic-slice")


def pass_of(op_name):
    """``"second"`` (a recompute region's second forward), ``"bwd"`` or
    ``"fwd"`` from one ``op_name``."""
    if "rematted_computation/" in op_name:
        return "second"
    return "bwd" if "transpose(jvp(" in op_name else "fwd"


def scope_of(op_name):
    """``jit(step)/transpose(jvp(mul.226))/dot_general`` -> ``mul.226``:
    the first path component that is a Program op's scope
    (``<type>.<seq>``) or a serving scope, bare or inside ``jvp(...)``
    / ``transpose(jvp(...))``; None where there is none."""
    for part in op_name.split("/"):
        m = _SCOPE.match(part)
        if m:
            return m.group(1)
    return None


def leaves(type_text):
    """``((dtype, shape), ...)`` of an HLO type as it is printed, layouts
    dropped and tuples flattened."""
    return tuple(
        (dtype, tuple(int(d.lstrip("<=")) for d in dims.split(",") if d))
        for dtype, dims in _LEAF.findall(type_text))


def nbytes(leaf):
    dtype, shape = leaf
    size = _BYTES.get(dtype, 1 if dtype.startswith("f8") else 0)
    for d in shape:
        size *= d
    return size


def _elements(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def _close(text, start):
    """The index of the ``)`` that closes the ``(`` at ``start``."""
    end = text.find(")", start)
    if end < 0:
        return len(text) - 1
    if text.find("(", start + 1, end) < 0:
        return end
    depth = 0
    for i in range(start, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if not depth:
                return i
    return len(text) - 1


def _attr(tail, key, close=None):
    """The value of ``key`` (``"dim_labels="``) in an instruction's
    attributes: up to ``close`` where it is bracketed, else up to the
    next comma; None where the key is absent."""
    at = tail.find(key)
    if at < 0:
        return None
    at += len(key)
    end = tail.find(close, at) + 1 if close else tail.find(",", at)
    return tail[at:end if end > 0 else len(tail)]


def _ints(braced):
    return [int(d) for d in re.findall(r"\d+", braced or "")]


def _lines(text):
    if not isinstance(text, str):
        yield from text
        return
    at, n = 0, len(text)
    while at < n:
        end = text.find("\n", at)
        if end < 0:
            end = n
        yield text[at:end]
        at = end + 1


def _contracted(opcode, tail, lhs, rhs):
    """The size a ``dot`` / ``convolution`` sums over for one element
    of its result."""
    if opcode == "dot":
        size = 1
        for d in _ints(_attr(tail, "lhs_contracting_dims=", "}")):
            size *= lhs[d]
        return size
    labels = _attr(tail, "dim_labels=") or ""
    kernel = labels.split("_")[-1].split("->")[0]
    size = rhs[kernel.index("i")] if "i" in kernel and rhs else 1
    window = _attr(tail, "window=", "}") or ""
    for d in _ints(_attr(window, "size=", " ")):
        size *= d
    return size


class _Computation:
    """What one computation of the text leaves: its summary as a fused
    computation (``dots``, ``scopes``, ``nested``, ``passes``, what each
    parameter is read through, the root) and, as one that is not, the
    instructions its rows are made from (``work``: made only for the
    computations the entry reaches) and the computations it runs
    (``callees``)."""

    def __init__(self, name):
        self.name = name
        self.types = {}       # instruction -> its type as printed
        self.params = {}      # parameter number -> instruction
        self.alias = {}       # a parameter or a bitcast of one -> itself
        self.reads = {}       # parameter -> [bytes, or None for all of it]
        self.updates = {}     # a dynamic-update-slice -> the update's bytes
        self.dots, self.scopes, self.nested = [], {}, set()
        self.passes = set()
        self.root = None      # (opcode, name, operand names)
        self.work, self.callees = [], []

    def leaf(self, name):
        got = leaves(self.types.get(name, ""))
        return got[0] if got else ("", ())

    def read(self, number, declared):
        """The bytes the body reads of parameter ``number``."""
        uses = self.reads.get(self.params.get(number), [None])
        if None in uses:
            return declared
        return min(declared, sum(uses))

    def written(self, declared):
        """The bytes of the results the body writes: the update where a
        result is a ``dynamic-update-slice``."""
        if self.root is None:
            return sum(declared)
        opcode, name, operands = self.root
        parts = operands if opcode == "tuple" else [name]
        if len(parts) != len(declared):
            return sum(declared)
        return sum(self.updates.get(p, d) for p, d in zip(parts, declared))


def kernel_rows(text):
    """``(module, rows)`` of a compiled step's HLO text (a string, or
    any iterable of its lines): the module's name and the rows the
    module's docstring describes, the entry's first and then each loop
    body's and branch's."""
    module, done, comp, entry = None, {}, None, None
    scopes = {}               # op_name -> (scope, pass): names repeat
    for line in _lines(text):
        if comp is None:
            if line.startswith("HloModule "):
                module = line[10:].split(",")[0].split()[0]
            elif line.endswith("{") and " -> " in line:
                m = _COMPUTATION.match(line)
                if m:
                    comp = _Computation(m.group(2))
                    if m.group(1):
                        entry = comp.name
            continue
        if line.startswith("}"):
            done[comp.name] = comp
            comp.types = comp.alias = None
            comp = None
            continue
        head = _HEAD.match(line)
        if head:
            _instruction(comp, done, scopes, line, head)
    rows, seen, queue = [], set(), [entry] if entry else []
    while queue:
        name = queue.pop(0)
        if name in seen or name not in done:
            continue
        seen.add(name)
        rows += [_finished(*work) for work in done[name].work]
        queue += done[name].callees
    return module, rows


def _instruction(comp, done, scopes, line, head):
    is_root, name = bool(head.group(1)), head.group(2)
    at = head.end()
    end = _close(line, at) + 1 if line[at] == "(" else line.find(" ", at)
    type_text = comp.types[name] = line[at:end]
    opens = line.find("(", end)
    opcode = line[end + 1:opens]
    closes = _close(line, opens)
    if opcode == "parameter":
        comp.params[int(line[opens + 1:closes])] = comp.alias[name] = name
        return
    if opcode == "constant":
        return
    inside = line[opens + 1:closes]
    operands = _NAME.findall(inside) if "%" in inside else [
        part.split()[-1] for part in inside.split(",") if part.strip()]
    tail = line[closes + 1:]
    at = tail.find('op_name="')
    op_name = tail[at + 9:tail.find('"', at + 9)] if at >= 0 else None
    if op_name is None:
        scope = which = None
    elif op_name in scopes:
        scope, which = scopes[op_name]
    else:
        scope, which = scopes[op_name] = (scope_of(op_name),
                                          pass_of(op_name))
    if is_root:
        comp.root = (opcode, name, operands)
    # -- as an instruction of a fused computation's body
    inner = None
    if opcode == "fusion":
        inner = done.get((_attr(tail, "calls=") or "").lstrip("%"))
    if inner is not None:
        comp.dots += inner.dots
        comp.passes |= inner.passes
        for s, n in inner.scopes.items():
            comp.scopes[s] = comp.scopes.get(s, 0) + n
        comp.nested.update(s for s in inner.scopes if s)
    else:
        comp.scopes[scope or ""] = comp.scopes.get(scope or "", 0) + 1
        if which:
            comp.passes.add(which)
    dot = None
    if opcode in ("dot", "convolution"):
        lhs, rhs = (comp.leaf(o)[1] for o in operands[:2])
        result = leaves(type_text)[0][1]
        size = _contracted(opcode, tail, lhs, rhs)
        dot = (op_name, lhs, rhs, result, size,
               2 * _elements(result) * size)
        comp.dots.append(dot)
    for i, operand in enumerate(operands):
        param = comp.alias.get(operand)
        if param is None:
            continue
        if opcode == "bitcast":
            comp.alias[name] = param
        elif opcode in _SLICES and i == 0:
            comp.reads.setdefault(param, []).append(
                sum(map(nbytes, leaves(type_text))))
        else:        # in place where it is the array a slice is put into
            comp.reads.setdefault(param, []).append(
                0 if opcode == "dynamic-update-slice" and i == 0 else None)
    if opcode == "dynamic-update-slice" and len(operands) > 1:
        comp.updates[name] = nbytes(comp.leaf(operands[1]))
    elif opcode == "bitcast" and operands and operands[0] in comp.updates:
        comp.updates[name] = comp.updates[operands[0]]
    # -- as a device op of a computation that is not fused
    if opcode in _NO_WORK:
        return
    for key in _CALLEES.get(opcode, ()):
        value = _attr(tail, key, "}" if key.startswith("branch") else None)
        comp.callees += _NAME.findall(value or "")
    cycles = _CYCLES.search(tail) if "estimated_cycles" in tail else None
    comp.work.append(({
        "name": name, "opcode": opcode, "computation": comp.name,
        "fusion_kind": _attr(tail, "kind=") if opcode == "fusion" else None,
        "custom_call_target": (_attr(tail, "custom_call_target=") or ""
                               ).strip('"') or None
        if opcode == "custom-call" else None,
        "op_name": op_name, "root_scope": scope,
        "estimated_cycles": int(cycles.group(1)) if cycles else None},
        type_text, [comp.types.get(o, "") for o in operands], inner, dot,
        which, comp.updates.get(name)))


def _finished(row, type_text, operand_types, inner, dot, which, update):
    """A row with what is read from its types, which only the rows of a
    computation the entry reaches are worth."""
    operands = [leaves(t) for t in operand_types]
    declared = [sum(map(nbytes, ls)) for ls in operands]
    results = leaves(type_text)
    result_bytes = [nbytes(leaf) for leaf in results]
    row.update(operands=tuple(l for ls in operands for l in ls),
               results=results)
    if inner is not None:
        row.update(
            bytes_in=sum(inner.read(i, d) for i, d in enumerate(declared)),
            bytes_out=inner.written(result_bytes),
            dots=tuple(inner.dots), scopes=dict(inner.scopes),
            nested=tuple(sorted(inner.nested)),
            passes=tuple(sorted(inner.passes)))
        return row
    opcode = row["opcode"]
    bytes_in, bytes_out = sum(declared), sum(result_bytes)
    if opcode in _SLICES:
        bytes_in = bytes_out + sum(declared[1:])
    elif opcode == "dynamic-update-slice":
        bytes_in, bytes_out = sum(declared[1:]), update or bytes_out
    elif opcode.endswith("-start"):          # an asynchronous pair books
        bytes_out = 0                        # its operands to the start
    elif opcode.endswith("-done"):           # and its result to the done
        bytes_in = 0
    row.update(bytes_in=bytes_in, bytes_out=bytes_out,
               dots=(dot,) if dot else (),
               scopes={row["root_scope"] or "": 1}, nested=(),
               passes=(which,) if which else ())
    return row
