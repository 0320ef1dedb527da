"""Program regions of the train step, read from the device trace.

The program runs each region of its train step under a fixed
``jax.named_scope`` (``REGIONS``). XLA keeps each HLO instruction's scope
path as its ``op_name`` metadata, e.g.
``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/dot_general``,
and the profiler stores every program's HLO, metadata included, in the
trace's ``/host:metadata`` plane: one ``Hlo Proto`` stat per program. The
``XLA Ops`` events on the device line carry only the instruction's name.

``op_paths`` reads the paths of the train step's module (``MODULES``) from
that plane; ``region_map`` maps each instruction name to the innermost
region on its path; ``region_seconds`` applies the map to a traced window's
innermost device ops. A region's forward, backward and recomputed ops all
count to it: the transform markers JAX wraps a scope in (``jvp(mlp)``,
``transpose(jvp(mlp))``) are taken off. Where the trace holds no region at
all (a program without the scopes) or one name maps to two regions, there
is nothing to read and the readers return None.
"""
from __future__ import annotations

import os
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench import trace
from chipbench.common import HERE, load_json, log

REGIONS = ("embed", "attn", "mlp", "norm", "lm_head", "optimizer")
MODULES = ("jit_train_step", "jit_pipeline_train_step")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


# --------------------------------------------------------------- scope paths
def region_of(path: str) -> Optional[str]:
    """The innermost of ``REGIONS`` on a scope path, or None."""
    found = None
    for part in path.split("/"):
        m = _TRANSFORM.match(part)
        while m:
            part = m.group(1)
            m = _TRANSFORM.match(part)
        if part in REGIONS:
            found = part
    return found


def region_map(paths: Dict[str, List[str]]) -> Optional[Dict[str, str]]:
    """{instruction name: region} from {instruction name: [scope paths]}
    (one path per module that holds the name); None when no path names a
    region or one name maps to two regions."""
    out: Dict[str, str] = {}
    for op, ps in paths.items():
        found = {r for r in map(region_of, ps) if r is not None}
        if len(found) > 1:
            log(f"scopes: {op} maps to {sorted(found)}: not read")
            return None
        if found:
            out[op] = found.pop()
    return out or None


def region_seconds(events: List[trace.Event], regions: Dict[str, str]
                   ) -> Dict[Optional[str], float]:
    """Device seconds of the innermost ops per region (None: no region)."""
    tot: Dict[Optional[str], float] = defaultdict(float)
    for name, _, d in trace.leaves(events):
        tot[regions.get(name)] += d / 1e9
    return dict(tot)


# ------------------------------------------------------ the trace's HLO protos
def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        s += 7
        if x < 0x80:
            return r, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a serialized protobuf message; a
    length-delimited value is its bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield f, v


def _first(b: bytes, field: int, default=b""):
    for f, v in _fields(b):
        if f == field:
            return v
    return default


def hlo_protos(xspace: bytes) -> Iterator[bytes]:
    """The serialized ``HloProto`` of each program in an ``XSpace``
    (tsl/profiler/protobuf/xplane.proto): XSpace.planes = 1; XPlane.name =
    2, .event_metadata = 4 and .stat_metadata = 5 (maps: key 1, value 2);
    XEventMetadata.stats = 5; XStatMetadata.name = 2; XStat.metadata_id =
    1, .bytes_value = 6."""
    for f, plane in _fields(xspace):
        if f != 1 or _first(plane, 2) != METADATA_PLANE.encode():
            continue
        stat_ids = set()
        for g, entry in _fields(plane):
            if g == 5 and _first(_first(entry, 2), 2) == HLO_PROTO_STAT.encode():
                stat_ids.add(_first(entry, 1, 0))
        for g, entry in _fields(plane):
            if g != 4:
                continue
            for h, stat in _fields(_first(entry, 2)):
                if h == 5 and _first(stat, 1, 0) in stat_ids:
                    yield _first(stat, 6)


def module_paths(hlo_proto: bytes) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: op_name}) of one ``HloProto``
    (xla/service/hlo.proto): HloProto.hlo_module = 1; HloModuleProto.name
    = 1, .computations = 3; HloComputationProto.instructions = 2;
    HloInstructionProto.name = 1, .metadata = 7; OpMetadata.op_name = 2."""
    module = _first(hlo_proto, 1)
    paths: Dict[str, str] = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, inst in _fields(comp):
            if g == 2:
                path = _first(_first(inst, 7), 2).decode()
                if path:
                    paths[_first(inst, 1).decode()] = path
    return _first(module, 1).decode(), paths


def op_paths(xplane_path: str) -> Dict[str, List[str]]:
    """{instruction name: [scope path per module]} of the train step's
    modules in one ``.xplane.pb``."""
    with open(xplane_path, "rb") as f:
        xspace = f.read()
    out: Dict[str, List[str]] = defaultdict(list)
    for proto in hlo_protos(xspace):
        name, paths = module_paths(proto)
        if name in MODULES:
            for op, p in paths.items():
                out[op].append(p)
    return dict(out)


def cell_trace(ctx) -> Optional[str]:
    """The newest ``.xplane.pb`` of the cell ``ctx`` reads. The context
    names no cell: it is the cell of ``BENCHMARK.json`` whose configuration
    and traffic files hold ``ctx.config`` and ``ctx.mix``, traced under
    ``.trace/<cell>`` (``CellRun.trace_dir``)."""
    bench = load_json(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "BENCHMARK.json"))
    for w in bench["workloads"]:
        if (load_json(os.path.join(HERE, "configs", w["config"] + ".json"))
                == ctx.config
                and load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
                == ctx.mix):
            try:
                return trace.latest_xplane(os.path.join(HERE, ".trace", w["name"]))
            except FileNotFoundError:
                return None
    return None


# ------------------------------------------------------------------ readers
def seconds(ctx) -> Optional[Dict[Optional[str], float]]:
    """Device seconds per region of the traced window, summed over the
    cell's devices (None: ops in no region), or None when the trace has no
    regions to read. Computed once per trace context; the seconds and the
    unattributed share of the ops' time go to standard error."""
    if not hasattr(ctx, "scope_seconds"):
        ctx.scope_seconds = _seconds(ctx)
    return ctx.scope_seconds


def _seconds(ctx) -> Optional[Dict[Optional[str], float]]:
    path = cell_trace(ctx)
    regions = region_map(op_paths(path)) if path else None
    if regions is None:
        log("scopes: the trace names no region of the train step")
        return None
    tot: Dict[Optional[str], float] = defaultdict(float)
    for events in ctx.device.values():
        for k, v in region_seconds(events, regions).items():
            tot[k] += v
    total = sum(tot.values())
    if total:
        per = ", ".join(f"{k or 'unattributed'} {v!r}" for k, v in
                        sorted(tot.items(), key=lambda kv: -kv[1]))
        log(f"scopes: device s per region: {per}; unattributed "
            f"{100.0 * tot.get(None, 0.0) / total!r}% of device op time")
    return dict(tot)
