"""Device time per ``jax.named_scope`` of the program in a traced run.

The forecasting policy wraps its weekly refit and forecast in
``jax.named_scope("forecast")``, and its stack, spot and convertible
solve in ``jax.named_scope("solve")`` (``core/policy.py``,
``core/replan.py``).  The scope reaches each compiled instruction's
``op_name`` (``jit(scan)/.../while/body/forecast/...``).  A TPU profile
names each device op by its HLO instruction only, but keeps every
compiled module's HLO (the ``Hlo Proto`` stats of the ``/host:metadata``
plane), where the ``op_name`` is: an op is in a scope when its
instruction's ``op_name`` has the scope as one of its ``/``-separated
parts (a fusion without one takes it from the computation it calls).
An op's module is the one running on its device around it ("XLA
Modules").  The device ops and modules are ``trace.load``'s events, the
window check is ``stages.of``'s; only the HLO is read here.

The readers sum, over the traced plans, the union of the intervals of
the ops in a scope, on the profile the harness leaves under the checkout
(``.bench_trace/``), as ``lib/stages`` does for the host stages.  A
profile whose ``plan`` window is not the record's belongs to another run
and gives nothing; so does a program whose ops carry no such scope.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

from lib import stages
from lib import trace as tr

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def fields(buf: bytes):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, bytes for a length-delimited field (fixed-width fields
    are skipped)."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def hlo_texts(data: bytes) -> dict[str, str]:
    """Module name (``jit_scan(12)``, as the device's "XLA Modules" line
    names it) -> HLO text, from the ``Hlo Proto`` stats of a serialized
    XSpace's ``/host:metadata`` plane (XSpace.planes = 1; XPlane.name = 2,
    event_metadata = 4, stat_metadata = 5; XEventMetadata.name = 2,
    stats = 5; XStat.metadata_id = 1, bytes_value = 6; HloProto.hlo_module
    = 1)."""
    from jax._src.lib import xla_client

    for num, plane in fields(data):
        if num != 1:
            continue
        parts = {4: [], 5: []}
        name = None
        for k, v in fields(plane):
            if k == 2:
                name = v.decode()
            elif k in parts:
                parts[k].append(dict(fields(v)).get(2, b""))
        if name != METADATA_PLANE:
            continue
        stat_names = {d.get(1): d.get(2, b"").decode()
                      for d in map(lambda m: dict(fields(m)), parts[5])}
        out = {}
        for meta in parts[4]:
            meta = list(fields(meta))
            mod = dict(meta).get(2, b"").decode()
            for k, stat in meta:
                st = dict(fields(stat)) if k == 5 else {}
                if stat_names.get(st.get(1)) == HLO_PROTO_STAT and 6 in st:
                    proto = dict(fields(st[6])).get(1)
                    if proto is not None:
                        out[mod] = xla_client._xla.HloModule.\
                            from_serialized_hlo_module_proto(proto).to_string()
        return out
    return {}


_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def _part(scope: str):
    return re.compile(rf"(?:^|/){re.escape(scope)}(?:/|$)")


def scoped_instructions(text: str, scope: str) -> set[str]:
    """Names of the instructions of one module's HLO ``text`` in
    ``scope``: their ``op_name`` has it as a part, or, without an
    ``op_name``, an instruction of a computation they call has."""
    part = _part(scope)
    comp, own, calls, comp_in = None, {}, {}, set()
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None and line.rstrip().endswith("{"):
                comp = c.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else None
        callee = _CALLS.search(line)
        calls[name] = callee.group(1) if callee else None
        if op and part.search(op.group(1)) and comp is not None:
            comp_in.add(comp)
    return {n for n, op in own.items()
            if (part.search(op) if op is not None else calls[n] in comp_in)}


def load(log_dir: str) -> tuple[list[dict], list[dict], dict[str, str]]:
    """(device ops, device modules, HLO text per module) of the newest
    ``.xplane.pb`` under ``log_dir`` (the events as ``trace.load`` reads
    them); empty where there is no profile."""
    try:
        events = tr.load(log_dir)
    except FileNotFoundError:
        return [], [], {}
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    with open(path, "rb") as f:
        texts = hlo_texts(f.read())
    device = [e for e in events if tr.DEVICE_PLANE.match(e["plane"])]
    return ([e for e in device if e["line"] == tr.OPS_LINE],
            [e for e in device if e["line"] == tr.MODULES_LINE], texts)


def in_scope(ops, modules, texts, scope: str) -> list[dict]:
    """The ops of ``ops`` in ``scope``: each op's instruction looked up in
    the HLO of the module running on its device around it."""
    names: dict[str, set] = {}
    spans: dict[str, list] = {}
    for m in sorted(modules, key=lambda m: m["start_ns"]):
        spans.setdefault(m["plane"], []).append(m)
    starts = {p: [m["start_ns"] for m in ms] for p, ms in spans.items()}
    out = []
    for e in ops:
        mods = spans.get(e["plane"], [])
        j = bisect.bisect_right(starts.get(e["plane"], []),
                                e["start_ns"]) - 1
        if j < 0 or e["start_ns"] >= mods[j]["start_ns"] + mods[j]["dur_ns"]:
            continue
        mod = mods[j]["name"]
        if mod not in names:
            text = texts.get(mod)
            names[mod] = (scoped_instructions(text, scope)
                          if text is not None else set())
        if tr.op_name(e["name"]).split(" ")[0].lstrip("%") in names[mod]:
            out.append(e)
    return out


def seconds(ops, window) -> float | None:
    """Seconds in ``window`` covered by ``ops``: the union of their
    intervals on each device (a scoped loop and the ops inside it count
    once), averaged over the devices that ran any; None where none did."""
    lo, hi = window
    per_device: dict[str, list] = {}
    for e in ops:
        s, t = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
        if t > s:
            per_device.setdefault(e["plane"], []).append((s, t))
    if not per_device:
        return None
    total = sum(b - a for iv in per_device.values()
                for a, b in tr._union(iv))
    return total / 1e9 / len(per_device)


def seconds_per_plan(record: dict, reader: str, scope: str):
    """Device seconds per traced plan in ``scope``, from the profile of
    the checkout that holds ``reader`` (a reader's ``__file__``); None
    where that profile is missing, is not the record's (``stages.of``),
    or has no op in ``scope``."""
    if stages.of(record, reader) is None:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader))))
    log_dir = os.path.join(root, stages.TRACE_DIR)
    window = tr.window_of(stages.load_host(log_dir), "plan")
    got = seconds(in_scope(*load(log_dir), scope), window)
    return None if got is None else got / record["plans"]
