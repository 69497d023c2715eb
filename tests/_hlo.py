"""Reading a compiled program's HLO text, for the tests that check what the
train step compiles to (``test_named_scopes.py`` and, through
``_mesh_worker.py``, ``test_mesh_cd_grab.py`` on the CPU,
``test_tpu_compile.py`` for a described v5e chip)."""
import collections
import re

import numpy as np

SCOPES = ("fwd_bwd", "grab_balance", "grad_accum", "optimizer",
          "grab_rollover")
# instructions that only move a buffer between memories or views
COPIES = ("copy", "copy-start", "copy-done", "bitcast")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")


def scope(op_name):
    for part in (op_name or "").split("/"):
        for word in re.findall(r"[A-Za-z0-9_]+", part):
            if word in SCOPES:
                return word
    return None


def hlo_bytes(shape: str) -> int:
    total = 0
    for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", shape):
        if dt in _DTYPE_BYTES:
            total += int(np.prod([int(d) for d in dims.split(",") if d])) \
                * _DTYPE_BYTES[dt]
    return total


def shape_dims(shape: str):
    m = re.match(r"([a-z]+\d*)\[([\d,]*)\]", shape)
    return m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)


def instructions(hlo: str) -> list:
    """Every instruction as a dict: its computation, whether that is a
    fusion's body or the entry, name, shape, opcode, ``op_name``, the
    computations it calls, for a loop its condition, body and known trip
    count, its operands, whether it is its computation's root and, for a
    tuple's element, the element's index."""
    out, comp, entry = [], None, False
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            comp, entry = m.group(1), line.startswith("ENTRY")
            continue
        m = _INSTR.match(line)
        if m and comp is not None:
            op = re.search(r'op_name="([^"]*)"', line)
            args = line[m.end():].split(")", 1)[0]
            index = re.search(r"\bindex=(\d+)", line)
            trips = re.search(r'"known_trip_count":\{"n":"(\d+)"', line)
            out.append({"comp": comp, "entry": entry, "name": m.group(1),
                        "shape": m.group(2), "opcode": m.group(3),
                        "op_name": op.group(1) if op else None,
                        "calls": re.findall(
                            r"(?:calls|to_apply)=%?([\w.\-]+)", line),
                        "loops": re.findall(
                            r"(?:condition|body)=%?([\w.\-]+)", line),
                        "operands": re.findall(r"%([\w.\-]+)", args),
                        "root": line.lstrip().startswith("ROOT "),
                        "index": int(index.group(1)) if index else None,
                        "trips": int(trips.group(1)) if trips else None})
    fused = {c for i in out if i["opcode"] == "fusion" for c in i["calls"]}
    for i in out:
        i["fused"] = i["comp"] in fused
        i["scope"] = scope(i["op_name"])
    return out


class Loop(collections.namedtuple("Loop", "body root readers stale_mean")):
    """The step's microbatch loop: its body's instructions, the body's root,
    each value's readers in the body (past copies) and, for each leaf of
    GraB's stale mean ``m_prev``, the body's value that holds it."""

    def reads(self, name: str) -> list:
        """The instructions of the body that read ``name``, looking past
        copies and bitcasts and leaving out the root (which only passes a
        carried value on)."""
        out, todo = [], [name]
        while todo:
            for r in self.readers[todo.pop()]:
                if r is self.root:
                    continue
                if r["opcode"] in COPIES:
                    todo.append(r["name"])
                else:
                    out.append(r)
        return out


def microbatch_loop(ins) -> Loop:
    """The train step's microbatch loop (the entry's one loop). The stale
    mean's leaves are found by name: the entry's parameters carry the
    argument's path (``state.grab.m_prev[...]``) as ``op_name``, and each
    reaches the loop's initial tuple, past copies, at the index under which
    the body reads it."""
    by_name = {i["name"]: i for i in ins if i["entry"]}
    loop = [i for i in by_name.values() if i["opcode"] == "while"]
    assert len(loop) == 1, [i["name"] for i in loop]
    init = by_name[loop[0]["operands"][0]]

    def source(name):
        while by_name[name]["opcode"] in COPIES:
            name = by_name[name]["operands"][0]
        return by_name[name]

    stale = {k for k, o in enumerate(init["operands"])
             if source(o)["opcode"] == "parameter"
             and (source(o)["op_name"] or "").startswith("state.grab.m_prev")}
    body = [i for i in ins if i["comp"] == loop[0]["loops"][-1]]
    param = [i["name"] for i in body if i["opcode"] == "parameter"]
    root = [i for i in body if i["root"]][0]
    readers = collections.defaultdict(list)
    for i in body:
        for o in i["operands"]:
            readers[o].append(i)
    stale_mean = [i for i in body if i["opcode"] == "get-tuple-element"
                  and i["operands"] == param and i["index"] in stale]
    return Loop(body, root, readers, stale_mean)


def runs(ins) -> dict:
    """How many times each computation runs per run of the entry: a loop's
    body runs its known trip count times per run of the loop, every other
    called computation once per run of its caller."""
    entry = {i["comp"] for i in ins if i["entry"]}
    callers = collections.defaultdict(list)
    for i in ins:
        for c in i["calls"]:
            callers[c].append((i["comp"], 1))
        for c in i["loops"]:
            assert i["trips"] is not None, i["name"]
            callers[c].append((i["comp"], i["trips"]))
    memo = {}

    def count(comp):
        if comp not in memo:
            memo[comp] = 1 if comp in entry else sum(
                count(p) * k for p, k in callers[comp])
        return memo[comp]
    return {i["comp"]: count(i["comp"]) for i in ins}


COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def collectives(hlo: str) -> list:
    """The program's collectives: each one's opcode, result dtype and dims
    (None for a tuple), bytes and how many times it runs per run of the
    program."""
    ins = instructions(hlo)
    n = runs(ins)
    out = []
    for i in ins:
        if i["opcode"] in COLLECTIVES:
            dtype, dims = (shape_dims(i["shape"])
                           if not i["shape"].startswith("(") else (None, None))
            out.append({"opcode": i["opcode"], "dtype": dtype,
                        "dims": None if dims is None else list(dims),
                        "bytes": hlo_bytes(i["shape"]),
                        "runs": n[i["comp"]]})
    return out
