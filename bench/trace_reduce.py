"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* Device planes are those named ``/device:TPU:<n>``.  Their ``XLA Ops``
  line gives the busy intervals (union of every operation's span); their
  ``XLA Modules`` line gives one event per program execution, with the
  program's name and id.
* Host planes give spans (``TraceAnnotation`` and JAX's own events).  An
  idle gap between two busy intervals of a device is put down to the
  span of the main thread that overlaps it most; of spans that overlap it
  equally, the shortest.
* Each operation's named scopes come from its op metadata: the device
  plane's event metadata holds, per program id and instruction, the
  ``tf_op`` path (the instruction's ``op_name``, such as
  ``jit(cand_step)/transpose(jvp(loss))/dot_general``).  XLA gives a
  fusion its root instruction's metadata, so a fusion takes its root's
  scope.  A fusion whose root carries none (a tuple, a bitcast, a
  scatter) has no ``tf_op``; its path then comes from the program's HLO,
  which the trace's ``/host:metadata`` plane holds: the instruction of
  its body nearest the root that carries an ``op_name``.  An operation's
  seconds are its self time (less the operations nested in it), so the
  seconds of a program's operations add up to the union of their
  intervals.

Times are seconds on the profiler's clock, which the device and host
planes share.
"""
from __future__ import annotations

import collections
import re
from dataclasses import dataclass, field

OTHER = "other"                     # the scope of an op with none known


@dataclass
class Event:
    name: str
    start: float
    end: float
    scopes: tuple = ()      # an op's named scopes (``attribute``)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Reduced:
    window: tuple = (0.0, 0.0)          # traced window on the trace's clock
    devices: list = field(default_factory=list)          # plane names
    busy: dict = field(default_factory=dict)     # device -> busy seconds
    modules: dict = field(default_factory=dict)  # device -> [Event]
    ops: dict = field(default_factory=dict)      # device -> [Event]
    gaps: dict = field(default_factory=dict)     # device -> [(s, e, span)]
    host: list = field(default_factory=list)     # [Event]
    # device -> {program: {scopes: self seconds}}; ``scopes`` is the
    # tuple ``scopes()`` gives, () for an op with none known
    scopes: dict = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        used = [b for b in self.busy.values() if b > 0]
        return sum(used) / len(used) if used else 0.0

    def _tables(self, program: str) -> list:
        """``{scopes: s}`` of the programs whose name holds ``program``,
        summed over them, one for each device that ran such a program."""
        out = []
        for dev in self.devices:
            progs = [t for p, t in self.scopes.get(dev, {}).items()
                     if program in p]
            if progs:
                tot: dict = collections.Counter()
                for t in progs:
                    tot.update(t)
                out.append(tot)
        return out

    def scope_s(self, program: str, scope: str) -> float | None:
        """Device seconds of the operations with named scope ``scope`` in
        their path (plain or inside a transform, so backward operations
        count too) in the programs whose name holds ``program``, averaged
        over the devices that ran such a program.  None where no such
        operation ran."""
        tables = self._tables(program)
        if not any(scope in sc for t in tables for sc in t):
            return None
        return sum(s for t in tables for sc, s in t.items()
                   if scope in sc) / len(tables)

    def scope_split(self, program: str) -> dict:
        """``{scope: s}``: the device seconds of the programs whose name
        holds ``program``, split by each operation's outermost named scope
        (``other`` where it has none), averaged over the devices that ran
        such a program.  The seconds add up to the union of the
        programs' operations' intervals."""
        tables = self._tables(program)
        out: dict = collections.Counter()
        for t in tables:
            for sc, s in t.items():
                out[sc[0] if sc else OTHER] += s / len(tables)
        return dict(out)

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` operations that took most device time, summed over
        the devices and averaged over them: ``[["op [scope]", s], ...]``,
        each named by its instruction and its outermost named scope."""
        tot = collections.Counter()
        for evs in self.ops.values():
            for e in evs:
                scope = e.scopes[0] if e.scopes else OTHER
                tot[f"{op_name(e.name)} [{scope}]"] += e.dur
        k = max(1, len([d for d, b in self.busy.items() if b > 0]))
        return [[name, s / k] for name, s in tot.most_common(n)]

    def top_gaps(self, n: int = 10) -> list:
        """Idle seconds by what the host was doing, averaged over the
        devices: ``[[span, s], ...]``."""
        tot = collections.Counter()
        for gs in self.gaps.values():
            for s, e, span in gs:
                tot[span] += e - s
        k = max(1, len([d for d, b in self.busy.items() if b > 0]))
        return [[name, s / k] for name, s in tot.most_common(n)]


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), calls=...`` -> ``fusion.12``."""
    head = hlo.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


_CALL = re.compile(r"([A-Za-z_][A-Za-z0-9_]*)\((.*)\)")
_FUNCTIONS = ("jit", "pjit")        # ``jit(f)`` names a call, not a scope
_MAPS = ("shard_map",)              # a mapped body, not a scope


def _in_program(op_path: str) -> bool:
    """Whether ``op_path`` starts with the program's call (``jit(f)/``):
    an argument's name or a reducer's bare ``add`` does not."""
    head = _CALL.fullmatch(op_path.split("/", 1)[0])
    return head is not None and head.group(1) in _FUNCTIONS


def scopes(op_path: str) -> tuple:
    """The named scopes of an operation's ``op_name`` path, outermost
    first: each part between the program and the operation itself, taken
    out of the transforms around it (``transpose(jvp(loss))`` is
    ``loss``); a call of a jitted function (``jit(silu)``) and a
    ``shard_map`` body are no scope.
    ``jit(cand_step)/shard_map/transpose(jvp(loss))/dot_general`` gives
    ``("loss",)``.  A ``:`` suffix (the trace's ``tf_op`` form) is
    ignored.  A path that does not start with the program's call (an
    argument's name) has none."""
    if not _in_program(op_path):
        return ()
    parts = op_path.rsplit(":", 1)[0].split("/")
    out = []
    for part in parts[1:-1]:
        m = _CALL.fullmatch(part)
        while m and m.group(1) not in _FUNCTIONS:
            part = m.group(2)
            m = _CALL.fullmatch(part)
        if m is None and part and part not in _MAPS:
            out.append(part)
    return tuple(out)


def _program_id(module: str) -> int | None:
    """``jit_ref_step(1234)`` -> 1234: the id the trace gives a program's
    operations."""
    head, _, rest = module.rpartition("(")
    return int(rest[:-1]) if head and rest[:-1].isdigit() else None


def _self_seconds(ops: list) -> list:
    """Each op's duration less the part of it that later ops, starting
    inside it, cover (ops sorted by start): their sum is the union of the
    ops' intervals."""
    own = [e.dur for e in ops]
    stack: list = []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            top = ops[stack[-1]]
            own[stack[-1]] -= min(e.end, top.end) - e.start
        stack.append(i)
    return own


def attribute(red: Reduced, meta: dict, hlo: dict | None = None) -> None:
    """Fill each op's ``scopes`` and ``red.scopes`` from ``meta``
    (``{device: {(program id, op event name): op path}}``) and, for an
    operation it has no path for, ``hlo`` (``{program id: {instruction:
    op path}}``): each device's operations take the program of the module
    event they start in."""
    hlo = hlo or {}
    for dev in red.devices:
        ops, mods, paths = red.ops[dev], red.modules[dev], meta.get(dev, {})
        tables: dict = {}
        j = 0
        for e, s in zip(ops, _self_seconds(ops)):
            while j < len(mods) and mods[j].end <= e.start:
                j += 1
            mod = mods[j] if j < len(mods) and mods[j].start <= e.start \
                else None
            sc = ()
            if mod is not None:
                pid = _program_id(mod.name)
                sc = scopes(paths.get((pid, e.name))
                            or hlo.get(pid, {}).get(op_name(e.name), ""))
                t = tables.setdefault(mod.name, {})
                t[sc] = t.get(sc, 0.0) + s
            e.scopes = sc
        red.scopes[dev] = tables


def _varint(b, i: int) -> tuple:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """``(field number, value)`` of a protobuf message's bytes ``b`` (a
    memoryview): an int for a varint, a memoryview for the rest."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")
        yield key >> 3, v


def op_paths(data: bytes) -> dict:
    """``{device plane: {(program id, op event name): op path}}`` from a
    serialized ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``): each
    device plane's event metadata with a ``program_id`` and a ``tf_op``
    stat.  Read with the wire format alone: no schema module is needed."""
    out = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:                            # XSpace.planes
            continue
        name, entries, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:                          # XPlane.name
                name = bytes(v).decode()
            elif f == 4:                        # event_metadata map entry
                entries.append(v)
            elif f == 5:                        # stat_metadata map entry
                for g, sm in _fields(v):
                    if g == 2:
                        d = dict(_fields(sm))
                        stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
        if not name.startswith("/device:TPU:"):
            continue
        paths = out.setdefault(name, {})
        for entry in entries:
            em = next((v for g, v in _fields(entry) if g == 2), None)
            if em is None:
                continue
            ev, stats = "", {}
            for g, v in _fields(em):
                if g == 2:                      # XEventMetadata.name
                    ev = bytes(v).decode()
                elif g == 5:                    # XEventMetadata.stats
                    st = dict(_fields(v))
                    key = stat_names.get(st.get(1))
                    if 5 in st:                 # str_value: tf_op
                        stats[key] = bytes(st[5]).decode()
                    elif 3 in st:               # uint64_value: program_id
                        stats[key] = st[3]
            if "tf_op" in stats and "program_id" in stats:
                paths[(stats["program_id"], ev)] = stats["tf_op"]
    return out


def _ints(v) -> list:
    """A repeated integer field's value: one varint, or packed."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def module_paths(module) -> dict:
    """``{instruction name: op path}`` of a serialized ``HloModuleProto``
    (``xla/service/hlo.proto``), every computation's instructions: an
    instruction's own ``op_name``, or for one without (a fusion whose
    root is a tuple, say) that of its called computations' root, or of
    the instruction nearest that root, operand by operand, that has one.
    Only a path inside the program (``_in_program``) counts; an
    instruction with none is left out."""
    comps = {}                      # id -> (root id, {id: instruction})
    for f, comp in _fields(memoryview(module)):
        if f != 3:                              # HloModuleProto.computations
            continue
        cid = root = None
        insts = {}
        for g, v in _fields(comp):
            if g == 5:                          # HloComputationProto.id
                cid = v
            elif g == 6:                        # .root_id
                root = v
            elif g == 2:                        # .instructions
                name, path, iid, operands, called = "", "", None, [], []
                for h, w in _fields(v):
                    if h == 1:                  # HloInstructionProto.name
                        name = bytes(w).decode()
                    elif h == 7:                # .metadata: OpMetadata
                        path = bytes(dict(_fields(w)).get(2, b"")).decode()
                        path = path if _in_program(path) else ""
                    elif h == 35:               # .id
                        iid = w
                    elif h == 36:               # .operand_ids
                        operands += _ints(w)
                    elif h == 38:               # .called_computation_ids
                        called += _ints(w)
                insts[iid] = (name, path, operands, called)
        comps[cid] = (root, insts)
    memo: dict = {}

    def body_path(cid) -> str:
        """The path nearest the root of computation ``cid``."""
        if cid in memo or cid not in comps:
            return memo.get(cid, "")
        memo[cid] = ""                          # a cycle finds nothing
        root, insts = comps[cid]
        queue, seen = [root], set()
        while queue:
            i = queue.pop(0)
            if i in seen or i not in insts:
                continue
            seen.add(i)
            _, path, operands, called = insts[i]
            path = path or next(
                (p for p in map(body_path, called) if p), "")
            if path:
                memo[cid] = path
                break
            queue += operands
        return memo[cid]

    out = {}
    for _, insts in comps.values():
        for name, path, _, called in insts.values():
            path = path or next((p for p in map(body_path, called) if p), "")
            if path:
                out[name] = path
    return out


def hlo_paths(data: bytes) -> dict:
    """``{program id: {instruction name: op path}}`` from a serialized
    ``XSpace``: the ``Hlo Proto`` stat of each event metadata of the
    ``/host:metadata`` plane, keyed by program id (``module_paths``)."""
    out = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:                            # XSpace.planes
            continue
        fields = list(_fields(plane))
        if next((bytes(v).decode() for f, v in fields if f == 2), "") \
                != "/host:metadata":
            continue
        for f, entry in fields:
            em = next((v for g, v in _fields(entry) if g == 2), None) \
                if f == 4 else None
            if em is None:
                continue
            pid, proto = None, None
            for g, v in _fields(em):
                if g == 1:                      # XEventMetadata.id
                    pid = v
                elif g == 5:                    # .stats: bytes_value
                    proto = dict(_fields(v)).get(6, proto)
            if pid is not None and proto is not None:
                module = next((v for g, v in _fields(proto) if g == 1),
                              None)             # HloProto.hlo_module
                if module is not None:
                    out[pid] = module_paths(module)
    return out


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(line) -> list:
    out = []
    for ev in line.events:
        s = ev.start_ns * 1e-9
        out.append(Event(ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def load(path: str, span: str = "bench.window") -> Reduced:
    """Reduce the trace at ``path`` over the host span named ``span`` (the
    measured window; the device events' extent where it is absent).  Idle
    gaps are put down to spans of the thread that recorded ``span``.

    The device planes' timestamps run about 2 ms ahead of the host's on a
    v5e (a program's device events start before the host call that
    dispatched it): gaps shorter than that are put down to the host's
    spans only roughly."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    red = Reduced()
    host_lines = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            red.devices.append(plane.name)
            red.modules[plane.name] = _events(lines["XLA Modules"]) \
                if "XLA Modules" in lines else []
            red.ops[plane.name] = _events(lines["XLA Ops"]) \
                if "XLA Ops" in lines else []
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_lines.append([e for e in _events(line) if e.dur > 0])
    red.devices.sort(key=lambda d: int(d.rsplit(":", 1)[-1]))
    for dev in red.devices:
        red.modules[dev].sort(key=lambda e: e.start)
        red.ops[dev].sort(key=lambda e: e.start)
    main = next((evs for evs in host_lines
                 if any(e.name == span for e in evs)), [])
    win = [e for e in main if e.name == span]
    if win:
        w0, w1 = win[0].start, win[0].end
    else:
        spans = [e for evs in red.ops.values() for e in evs]
        w0, w1 = ((min(e.start for e in spans), max(e.end for e in spans))
                  if spans else (0.0, 0.0))
    red.window = (w0, w1)
    clip = lambda evs: [e for e in evs if e.end > w0 and e.start < w1]
    red.host = [e for e in clip(main) if e.name != span]
    for dev in red.devices:
        red.modules[dev] = clip(red.modules[dev])
        red.ops[dev] = clip(red.ops[dev])
        busy = _union([(max(e.start, w0), min(e.end, w1))
                       for e in red.ops[dev]])
        red.busy[dev] = sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        red.gaps[dev] = _blame(gaps, red.host)
    attribute(red, op_paths(data), hlo_paths(data))
    return red


def _blame(gaps: list, host: list) -> list:
    """``[(start, end, span)]``: each gap with the host span that overlaps
    it most (the shortest of equals), by one sweep over both sorted
    lists."""
    host = sorted(host, key=lambda h: h.start)
    out, active, i = [], [], 0
    for s, e in gaps:
        while i < len(host) and host[i].start < e:
            active.append(host[i])
            i += 1
        active = [h for h in active if h.end > s]
        best, key = "no host span", (0.0, 0.0)
        for h in active:
            k = (min(e, h.end) - max(s, h.start), -h.dur)
            if k > key:
                best, key = h.name, k
        out.append((s, e, best))
    return out
