"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain structure, ``{plane: {line: [[name, start_ns, dur_ns, meta],
...]}}``, keeping the device planes' lines and the host's ``bench.*``
spans; ``meta`` joins an op's naming stats (its module, its long
name) so that a kernel is found under either.  ``reduce`` turns that
structure into a :class:`Reduced`: the traced window, each chip's busy time (the union of
its op intervals inside the window), per-op device time with the XLA
module each op ran in, and the longest idle gaps labelled by what the
host was doing.  Tests feed ``reduce`` a small recorded structure
(``bench/tests/data``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
HOST_SPANS = ("bench.prepare", "bench.solve", "bench.complete")
# Stats of a device event that name it, kept in its ``meta``.
META_STATS = ("hlo_module", "long_name", "tf_op", "hlo_op", "name")


def load(trace_dir: str) -> dict:
    """The recorded events of the newest ``.xplane.pb`` under
    ``trace_dir``."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime))
    out: dict = {}
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines: dict = {}
        for line in plane.lines:
            evs = []
            for e in line.events:
                if device:
                    evs.append([e.name, float(e.start_ns),
                                float(e.duration_ns), _meta(e)])
                elif e.name.startswith("bench."):
                    evs.append([e.name, float(e.start_ns),
                                float(e.duration_ns)])
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        out[plane.name] = lines
    return out


def op_name(hlo: str) -> str:
    """An op's own name: a TPU trace names each op by its whole HLO
    instruction (``%schwarz_fwd.9 = f32[...] custom-call(...)``), whose
    operand list would match other ops' patterns; keep what precedes
    `` = ``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _meta(event) -> str:
    stats = dict(event.stats)
    return " ".join(f"{k}={stats[k]}" for k in META_STATS
                    if isinstance(stats.get(k), str))


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


@dataclasses.dataclass
class Device:
    busy_ns: float
    ops: list            # [name, module, start_ns, dur_ns, text] in the
                         # window
    gaps: list           # idle [start_ns, end_ns] in the window


@dataclasses.dataclass
class Reduced:
    window_ns: tuple     # (start, end) of the bench.window span
    devices: list        # Device per chip, in chip order
    host: list           # [name, start_ns, end_ns] of the bench.* spans
    compiles: list       # [start_ns, end_ns] of backend compiles

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        return (sum(d.busy_ns for d in self.devices)
                / len(self.devices) * 1e-9)

    def op_seconds(self, op, module=None) -> float:
        """Device seconds, summed over chips, of ops whose name or meta
        matches the regex ``op`` and, when given, whose module matches
        ``module``."""
        op_re, mod_re = re.compile(op), re.compile(module or "")
        return sum(dur for d in self.devices
                   for _, mod, _, dur, text in d.ops
                   if op_re.search(text) and mod_re.search(mod or "")) \
            * 1e-9

    def top_ops(self, k: int = 10) -> list:
        """[name, seconds] of the k ops with most device time, summed
        over chips."""
        tot: dict = {}
        for d in self.devices:
            for name, _, _, dur, _ in d.ops:
                tot[name] = tot.get(name, 0.0) + dur
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, dur * 1e-9] for name, dur in top]

    def host_label(self, s: float, e: float) -> str:
        """What the host was doing over [s, e): the bench spans and
        compiles that overlap it, most overlap first."""
        share: dict = {}
        for name, hs, he in self.host:
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                share[name] = share.get(name, 0.0) + ov
        for cs, ce in self.compiles:
            ov = min(e, ce) - max(s, cs)
            if ov > 0:
                share["compile"] = share.get("compile", 0.0) + ov
        if not share:
            return "none"
        return "+".join(k.removeprefix("bench.") for k, _ in
                        sorted(share.items(), key=lambda kv: -kv[1]))

    def idle_gaps(self, k: int = 10) -> list:
        """[host label, seconds] of the k longest idle gaps of chip 0."""
        gaps = sorted(self.devices[0].gaps, key=lambda g: g[0] - g[1])[:k]
        return [[self.host_label(s, e), (e - s) * 1e-9] for s, e in gaps]


def _modules_of(ops, modules):
    """[name, module, start, dur, text] of each [name, start, dur, meta]
    op: its module is the ``hlo_module`` stat where the op carries one,
    else the module event that holds its start; ``text`` (name and meta)
    is what the op regexes search."""
    mods = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for name, s, dur, meta in sorted(ops, key=lambda o: o[1]):
        while j + 1 < len(mods) and mods[j + 1][1] <= s:
            j += 1
        mod = re.search(r"hlo_module=(\S+)", meta)
        if mod:
            mod = mod.group(1)
        elif mods and mods[j][1] <= s < mods[j][1] + mods[j][2]:
            mod = mods[j][0]
        out.append([name, mod, s, dur, f"{name} {meta}"])
    return out


def reduce(events: dict, n_devices: int, compiles=()) -> Reduced:
    """The traced window's device numbers.  ``compiles`` are backend
    compiles as (start, end) seconds from the window's start."""
    host = [[ev[0], ev[1], ev[1] + ev[2]]
            for line in events.get(HOST_PLANE, {}).values() for ev in line]
    wins = [h for h in host if h[0] == WINDOW]
    if not wins:
        raise RuntimeError("the trace holds no bench.window span")
    lo, hi = wins[0][1], wins[0][2]
    planes = sorted(((int(DEVICE_PLANE.match(p).group(1)), lines)
                     for p, lines in events.items()
                     if DEVICE_PLANE.match(p)), key=lambda t: t[0])
    devices = []
    for idx, lines in planes[:n_devices]:
        if OPS_LINE not in lines:
            raise RuntimeError(f"device {idx} of the trace has no "
                               f"{OPS_LINE!r} line (lines: {sorted(lines)})")
        ops = []
        for ev in lines[OPS_LINE]:
            cs, ce = _clip(ev[1], ev[1] + ev[2], lo, hi)
            if ce > cs:
                ops.append([op_name(ev[0]), cs, ce - cs,
                            ev[3] if len(ev) > 3 else ""])
        ops = _modules_of(ops, lines.get(MODULES_LINE, []))
        if ops and not any(o[1] for o in ops):
            raise RuntimeError(f"no op of device {idx} in the window can "
                               f"be put in its XLA module")
        busy = _union([[o[2], o[2] + o[3]] for o in ops])
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append([t, s])
            t = max(t, e)
        if t < hi:
            gaps.append([t, hi])
        devices.append(Device(busy_ns=sum(e - s for s, e in busy),
                              ops=ops, gaps=gaps))
    if len(devices) < n_devices:
        raise RuntimeError(f"the trace holds {len(devices)} device planes, "
                           f"the run used {n_devices}")
    return Reduced(window_ns=(lo, hi), devices=devices,
                   host=[h for h in host if h[0] in HOST_SPANS],
                   compiles=[[lo + s * 1e9, lo + e * 1e9]
                             for s, e in compiles])
