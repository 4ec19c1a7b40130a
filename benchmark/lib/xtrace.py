"""From the profiler's trace to numbers: device busy and idle time, time per
kernel, collective time that no compute hides, and the host span that covers
each idle gap. The arithmetic works on plain intervals (tested on hand-made
ones); ``load`` reads a recorded ``.xplane.pb`` with ``jax.profiler``.

Times inside a :class:`Trace` are nanoseconds on the profiler's clock.
"""

import glob
import os
import re
import shutil
import tempfile

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
UNATTRIBUTED = "host__unattributed_"


def base_name(name: str) -> str:
    """An operation's name without the compiler's numbering: ``fusion.12``
    and ``%fusion.3 = ...`` are both ``fusion``."""
    name = name.split(" = ")[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


def union_ns(intervals) -> int:
    """Length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(intervals, start, end):
    return [(max(s, start), min(e, end)) for s, e in intervals
            if e > start and s < end]


def gaps_ns(intervals, start, end):
    """The parts of ``[start, end]`` that no interval covers."""
    out, cur = [], start
    for s, e in sorted(clip(intervals, start, end)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if end > cur:
        out.append((cur, end))
    return out


def subtract_ns(intervals, covers) -> int:
    """Length of ``intervals`` that ``covers`` does not cover."""
    total = 0
    for s, e in intervals:
        total += (e - s) - union_ns(clip(covers, s, e))
    return total


class DeviceTrace:
    """One device: its operations ``(name, start, end)`` and the executions
    of whole programs ``(name, start, end)``."""

    def __init__(self, ops, modules):
        self.ops = sorted(ops, key=lambda o: o[1])
        self.modules = sorted(modules, key=lambda m: m[1])

    def within(self, start, end):
        return DeviceTrace(
            [o for o in self.ops if o[2] > start and o[1] < end],
            [m for m in self.modules if m[2] > start and m[1] < end])

    def intervals(self, start=None, end=None):
        iv = [(s, e) for _, s, e in self.ops]
        return iv if start is None else clip(iv, start, end)


class Trace:
    """A reduced trace: ``devices`` by id, the benchmark's host spans
    ``(name, start, end, index)`` (``index`` as ``lib/spans.py`` numbered
    them, or None), and the window ``(start, end)`` they are read in."""

    def __init__(self, devices: dict, host_spans, window=None):
        self.devices = devices
        self.host_spans = sorted(host_spans, key=lambda s: s[1])
        if window is None:
            starts = [o[1] for d in devices.values() for o in d.ops]
            ends = [o[2] for d in devices.values() for o in d.ops]
            window = (min(starts), max(ends)) if starts else (0, 0)
        self.window = window

    def _per_device(self, fn):
        vals = [fn(d) for d in self.devices.values()]
        return sum(vals) / len(vals) if vals else 0.0

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return self._per_device(
            lambda d: union_ns(d.intervals(*self.window))) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def op_seconds(self) -> dict:
        """Seconds by operation name, averaged over the devices."""
        out = {}
        for d in self.devices.values():
            for name, s, e in d.ops:
                s, e = max(s, self.window[0]), min(e, self.window[1])
                if e > s:
                    key = base_name(name)
                    out[key] = out.get(key, 0.0) + (e - s) / 1e9
        return {k: v / len(self.devices) for k, v in out.items()}

    def op_calls(self, name: str):
        """Durations in seconds of every call of the operation ``name``
        inside the window, over all devices."""
        return [
            (e - s) / 1e9 for d in self.devices.values()
            for n, s, e in d.ops
            if base_name(n) == name and s >= self.window[0]
            and e <= self.window[1]
        ]

    def exposed_collective_s(self) -> float:
        """Collective time during which no compute operation runs on the
        same device, averaged over the devices."""
        def one(d):
            ops = d.within(*self.window).ops
            coll = clip([(s, e) for n, s, e in ops
                         if COLLECTIVE.match(base_name(n))], *self.window)
            comp = [(s, e) for n, s, e in ops
                    if not COLLECTIVE.match(base_name(n))]
            return subtract_ns(coll, comp)
        return self._per_device(one) / 1e9

    def idle_gaps(self) -> dict:
        """Idle seconds by the benchmark's host span that covers most of
        each gap (``host__unattributed_`` where none does), averaged over
        the devices."""
        out = {}
        for d in self.devices.values():
            for s, e in gaps_ns(d.intervals(), *self.window):
                name = self._covering_span(s, e)
                out[name] = out.get(name, 0.0) + (e - s) / 1e9
        return {k: v / len(self.devices) for k, v in out.items()}

    def _covering_span(self, start, end) -> str:
        best, best_cover = UNATTRIBUTED, 0
        for name, s, e, _ in self.host_spans:
            if s >= end:
                break
            cover = min(e, end) - max(s, start)
            # the innermost span wins a tie: later spans start later
            if cover > 0 and cover >= best_cover:
                best, best_cover = name, cover
        return best

    def breakdown(self, top: int = 10) -> dict:
        def ranked(d):
            return [[k, v] for k, v in sorted(
                d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": ranked(self.op_seconds()),
                "idle_gaps": ranked(self.idle_gaps())}

    def busy_within_spans(self, span_name: str):
        """For each host span of that name inside the window: (index, device
        busy nanoseconds inside it on the first device)."""
        dev = next(iter(self.devices.values()))
        iv = dev.intervals()
        return [
            (idx, union_ns(clip(iv, s, e)))
            for name, s, e, idx in self.host_spans
            if name == span_name and s >= self.window[0]
            and e <= self.window[1]
        ]


def steady_steps(trace: Trace, skip: int) -> Trace:
    """Narrow the window to whole executions of the step program: from the
    start of execution ``skip`` (the first ones refill the queue that
    starting the profiler drained) to the end of the last whole one. The step
    program is the one that took most of the device's time."""
    starts, ends = [], []
    if not trace.devices:
        return trace
    for d in trace.devices.values():
        by_name = {}
        for name, s, e in d.modules:
            by_name.setdefault(name, []).append((s, e))
        if not by_name:
            return trace
        runs = max(by_name.values(), key=lambda r: sum(e - s for s, e in r))
        if len(runs) <= skip:
            return trace
        starts.append(runs[skip][0])
        ends.append(runs[-1][1])
    return Trace(trace.devices, trace.host_spans, (min(starts), max(ends)))


def step_count(trace: Trace) -> int:
    """Whole executions of the step program inside the window (device 0)."""
    d = next(iter(trace.devices.values()))
    by_name = {}
    for name, s, e in d.modules:
        if s >= trace.window[0] and e <= trace.window[1]:
            by_name.setdefault(name, []).append((s, e))
    if not by_name:
        return 0
    return len(max(by_name.values(), key=lambda r: sum(e - s for s, e in r)))


def load(path: str, span_prefixes=("bench.", "batcher.", "engine.",
                                   "loadgen.")) -> Trace:
    """Read an ``.xplane.pb``: device planes ``/device:TPU:<n>`` (their
    ``XLA Ops`` and ``XLA Modules`` lines) and, from every other plane, the
    events whose names start with one of ``span_prefixes``: the spans that
    the benchmark wrote with ``jax.profiler.TraceAnnotation``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.name, int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns))
                           for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(base_name(ev.name.split("(")[0]),
                                int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns))
                               for ev in line.events]
            devices[int(m.group(1))] = DeviceTrace(ops, modules)
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefixes):
                        name, _, idx = ev.name.partition("#")
                        host.append((name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns),
                                     int(idx) if idx.isdigit() else None))
    return Trace(devices, host)


def record(fn, n_devices: int) -> Trace:
    """Run ``fn()`` under the profiler and return the reduced trace. The
    trace's files go under ``TMPDIR`` and are deleted once read."""
    import jax

    directory = tempfile.mkdtemp(prefix="benchmark-trace-")
    try:
        # the benchmark's spans are TraceMe events of the host tracer; the
        # Python tracer would add a callback to every call of the host's
        # threads, which are what the window measures
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(directory, profiler_options=options)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        trace = load(paths[0])
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    if len(trace.devices) not in (0, n_devices):
        raise RuntimeError(
            f"the trace holds {len(trace.devices)} device planes, the run "
            f"used {n_devices}")
    return trace


_ALL_REDUCE = re.compile(
    r'"?stablehlo\.all_reduce"?\(.*?replica_groups\s*=\s*dense<(\[?\[.*?\]\]?)>'
    r".*?\}\)?\s*:\s*\((.*?)\)\s*->", re.S)
_TENSOR = re.compile(r"tensor<((?:\d+x)*)(\w+)>")
_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "i32": 4, "ui32": 4, "i8": 1,
             "ui8": 1, "f64": 8, "i64": 8, "i1": 1}


def world_allreduce_bytes(stablehlo: str, world: int) -> int:
    """Bytes that the lowered step's all-reduces carry in groups that hold
    the whole world (0 on one chip, where there is no exchange to count)."""
    if world <= 1:
        return 0
    total = 0
    for m in _ALL_REDUCE.finditer(stablehlo):
        groups = re.findall(r"\[([\d,\s]+)\]", m.group(1)) or [m.group(1)]
        if not any(len(re.findall(r"\d+", g)) == world for g in groups):
            continue
        for dims, dtype in _TENSOR.findall(m.group(2)):
            n = 1
            for d in dims.split("x"):
                if d:
                    n *= int(d)
            total += n * _ITEMSIZE[dtype]
    return total
