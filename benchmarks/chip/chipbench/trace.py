"""Reduction of a profiler trace to device busy time, kernel time, and the
host phase behind each idle gap of the device.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but
JAX. Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation the device ran, named after its HLO instruction.
Host spans (``jax.profiler.TraceAnnotation``) sit on the host plane's
thread lines. Both are on one clock.
"""

from __future__ import annotations

import glob
import os
import re

HOST_PHASES = ("generate", "stage_dispatch", "fetch", "wait_frames")
WINDOW_SPAN = "trace_window"


def load(trace_dir: str) -> dict:
    """{"ops": [per device: sorted (start_s, end_s, op name)],
    "modules": [per device: sorted (start_s, end_s, program name)],
    "spans": [(name, start_s, end_s)]} of the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no trace written under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            evs, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs.extend((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                op_name(e.name)) for e in line.events)
                elif line.name == "XLA Modules":
                    mods.extend((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                 module_name(e.name)) for e in line.events)
            ops.append(sorted(evs))
            modules.append(sorted(mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_PHASES or e.name == WINDOW_SPAN:
                        spans.append((e.name, e.start_ns * 1e-9,
                                      e.end_ns * 1e-9))
    return {"ops": ops, "modules": modules,
            "spans": sorted(spans, key=lambda s: s[1])}


def op_name(text: str) -> str:
    """The HLO instruction name of an op event, whose name on the TPU is
    the instruction's whole text (``%fusion.13 = f32[...] fusion(...)``)."""
    m = re.match(r"\s*%?([\w.\-]+)\s*=", text)
    return m.group(1) if m else text


def module_name(text: str) -> str:
    """A program's name without its fingerprint: ``jit_counted(1234)``
    -> ``jit_counted``."""
    return re.sub(r"\(\d+\)$", "", text)


def module_s(tr: dict, name: str) -> float | None:
    """Mean device seconds of one execution of program ``name``, over the
    executions wholly inside the window, averaged over the devices."""
    lo, hi = window(tr)
    per = []
    for mods in tr.get("modules", []):
        d = [e - s for s, e, n in mods if n == name and s >= lo and e <= hi]
        if d:
            per.append(sum(d) / len(d))
    return sum(per) / len(per) if per else None


def union(intervals, lo: float, hi: float) -> list:
    """Merged intervals of ``intervals`` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(a, lo), min(b, hi)) for a, b, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged, lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def window(tr: dict) -> tuple[float, float]:
    w = [s for s in tr["spans"] if s[0] == WINDOW_SPAN]
    if not w:
        raise RuntimeError("the trace holds no window span")
    return w[0][1], w[0][2]


def busy_s(tr: dict) -> float:
    """Seconds in which any operation ran, averaged over the devices."""
    lo, hi = window(tr)
    per = [covered(union(o, lo, hi), lo, hi) for o in tr["ops"]]
    return sum(per) / max(len(per), 1)


def idle_gaps(tr: dict, top: int = 10) -> list:
    """Device idle seconds in the window, summed by the host phase that
    covers each gap's middle ("other" where none does), largest first."""
    lo, hi = window(tr)
    phases = [s for s in tr["spans"] if s[0] in HOST_PHASES]
    sums: dict[str, float] = {}
    for o in tr["ops"][:1]:
        m = union(o, lo, hi)
        edges = [lo] + [x for s, e in m for x in (s, e)] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            name = next((n for n, s, e in phases if s <= mid <= e), "other")
            sums[name] = sums.get(name, 0.0) + (b - a)
    return sorted(([k, v] for k, v in sums.items()),
                  key=lambda kv: -kv[1])[:top]


def op_family(name: str, kernels: dict) -> str:
    """An op's kernel name where the compiled step maps it to one, else
    its HLO name without the instance number."""
    if name in kernels:
        return kernels[name]
    return re.sub(r"\.\d+$", "", name)


def device_ops(tr: dict, kernels: dict, top: int = 10) -> list:
    """The operation families that took most device time in the window."""
    lo, hi = window(tr)
    sums: dict[str, float] = {}
    for o in tr["ops"][:1]:
        for s, e, name in o:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                fam = op_family(name, kernels)
                sums[fam] = sums.get(fam, 0.0) + d
    return sorted(([k, v] for k, v in sums.items()),
                  key=lambda kv: -kv[1])[:top]


def kernel_s(tr: dict, kernels: dict, kernel: str) -> tuple[float, int]:
    """(seconds, calls) of ``kernel``'s events in the window, averaged
    over the devices."""
    lo, hi = window(tr)
    secs, calls = [], []
    for o in tr["ops"]:
        ev = [(s, e) for s, e, n in o
              if kernels.get(n) == kernel and s >= lo and e <= hi]
        secs.append(sum(e - s for s, e in ev))
        calls.append(len(ev))
    n = max(len(secs), 1)
    return sum(secs) / n, int(round(sum(calls) / n))


def kernel_map(hlo_text: str) -> dict:
    """HLO instruction name -> kernel name, for every Pallas kernel
    (``tpu_custom_call``) in a compiled program's text. Pallas names the
    custom call after the kernel's function, so the map strips only the
    instance number."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if m:
            out[m.group(1)] = re.sub(r"\.\d+$", "", m.group(1))
    return out
