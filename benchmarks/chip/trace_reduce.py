"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

A TPU trace has one plane per chip (``/device:TPU:<n>``) with a line of
program executions (``XLA Modules``, named ``jit_<fn>(<hash>)``) and a
line of operations (``XLA Ops``, named by their HLO text,
``%<op>.<n> = ...``; a Pallas kernel's op is named after its kernel,
e.g. ``%plam_matmul.80``).  Host spans (``jax.profiler.TraceAnnotation``)
sit on the host plane's ``python`` line, on the same clock.

The reduction keeps, per chip, the operation intervals and which
program each ran in, and the host spans of the harness and the engine
(names starting ``bench.``, ``engine.`` or ``serve.``).  The window is
the harness's ``bench.window`` span.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SPAN_PREFIXES = ("bench.", "engine.", "serve.")
_OP_NAME = re.compile(r"^%?([^\s=]+)")
_SUFFIX = re.compile(r"\.\d+$")


def op_kind(name: str) -> str:
    """``%plam_matmul.80 = f32[...] ...`` -> ``plam_matmul``."""
    m = _OP_NAME.match(name)
    return _SUFFIX.sub("", m.group(1) if m else name)


@dataclasses.dataclass
class Chip:
    start: np.ndarray  # op starts, ns, sorted
    end: np.ndarray
    kind: List[str]  # op kinds
    module: np.ndarray  # index into module_names per op (-1: none)
    module_names: List[str]
    mod_start: np.ndarray  # program executions
    mod_end: np.ndarray
    mod_name: List[str]


@dataclasses.dataclass
class Reduced:
    chips: List[Chip]
    spans: List[Tuple[str, float, float]]  # (name, start ns, end ns)
    window: Tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    # -- device time ------------------------------------------------------

    def _in(self, chip: Chip, span) -> np.ndarray:
        lo, hi = span
        return (chip.start >= lo) & (chip.end <= hi)

    def busy_intervals(self, chip: Chip, span=None) -> np.ndarray:
        """Union of the chip's op intervals inside ``span``: [n, 2] ns."""
        lo, hi = span or self.window
        s = np.clip(chip.start, lo, hi)
        e = np.clip(chip.end, lo, hi)
        keep = e > s
        s, e = s[keep], e[keep]
        if not len(s):
            return np.zeros((0, 2))
        order = np.argsort(s, kind="stable")
        s, e = s[order], np.maximum.accumulate(e[order])
        new = np.empty(len(s), bool)
        new[0] = True
        new[1:] = s[1:] > e[:-1]
        idx = np.flatnonzero(new)
        return np.stack([s[idx], np.r_[e[idx[1:] - 1], e[-1]]], axis=1)

    def busy_in(self, spans) -> List[float]:
        """Busy seconds inside each of ``spans``, averaged over the chips."""
        per_chip = []
        for c in self.chips:
            iv = self.busy_intervals(c)
            cum = np.r_[0.0, np.cumsum(iv[:, 1] - iv[:, 0])]

            def covered(t):  # busy ns in [window start, t]
                i = np.searchsorted(iv[:, 0], t, side="right")
                part = np.minimum(t, iv[i - 1, 1]) - iv[i - 1, 0] if i else 0.0
                return cum[max(i - 1, 0)] + max(part, 0.0) if i else 0.0

            per_chip.append([(covered(e) - covered(s)) * 1e-9 for s, e in spans])
        return list(np.mean(per_chip, axis=0)) if per_chip else [0.0] * len(spans)

    def busy_s(self, win_span=None) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        tot = [np.sum(np.diff(self.busy_intervals(c, win_span), axis=1)) for c in self.chips]
        return float(np.mean(tot)) * 1e-9 if tot else 0.0

    def kernel_s(self, names: Sequence[str]) -> float:
        """Device seconds of ops whose kind contains one of ``names``,
        inside the window, averaged over the chips."""
        tot = []
        for c in self.chips:
            inside = self._in(c, self.window)
            hit = np.array([any(n in k for n in names) for k in c.kind], bool)
            tot.append(float(np.sum((c.end - c.start)[inside & hit])))
        return float(np.mean(tot)) * 1e-9 if tot else 0.0

    def program_calls(self, contains_kernel: Optional[str] = None,
                      name_prefix: Optional[str] = None) -> List[float]:
        """Device seconds of each program execution in the window (first
        chip) that holds an op of kind ``contains_kernel`` or whose name
        starts with ``name_prefix``."""
        if not self.chips:
            return []
        c = self.chips[0]
        names = set()
        if contains_kernel is not None:
            for k, m in zip(c.kind, c.module):
                if m >= 0 and contains_kernel in k:
                    names.add(c.module_names[m])
        if name_prefix is not None:
            names |= {n for n in c.module_names if n.startswith(name_prefix)}
        lo, hi = self.window
        return [(e - s) * 1e-9 for s, e, n in zip(c.mod_start, c.mod_end, c.mod_name)
                if n in names and s >= lo and e <= hi]

    # -- host spans -------------------------------------------------------

    def spans_named(self, name: str, inside_window: bool = True):
        lo, hi = self.window
        return [(s, e) for n, s, e in self.spans
                if n == name and (not inside_window or (s >= lo and e <= hi))]

    def host_label(self, t0: float, t1: float) -> str:
        """What the host did in the gap [t0, t1]: the innermost span open
        at its middle; inside ``engine.step`` but between its phases, also
        the phase the host was about to start (or ``end`` of the step)."""
        t = (t0 + t1) / 2
        best, best_start, best_end = "host:outside-spans", -np.inf, np.inf
        for n, s, e in self.spans:
            if s <= t < e and s > best_start:
                best, best_start, best_end = n, s, e
        if best == "engine.step":
            nxt = [(s, n) for n, s, e in self.spans
                   if n.startswith("serve.") and t <= s < best_end]
            best += " before " + min(nxt)[1] if nxt else " end"
        return best

    def idle_gaps(self, top: int) -> List[Tuple[str, float]]:
        """The ``top`` longest idle gaps of the first chip in the window,
        each labelled by the host span open at its middle."""
        if not self.chips:
            return []
        busy = self.busy_intervals(self.chips[0])
        lo, hi = self.window
        edges = np.r_[lo, busy.ravel(), hi].reshape(-1, 2)  # (gap start, gap end)
        gaps = [(s, e) for s, e in edges if e > s]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self.host_label(s, e), (e - s) * 1e-9) for s, e in gaps[:top]]

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops: Dict[str, float] = {}
        if self.chips:
            c = self.chips[0]
            # a loop op (``while``) holds its body's ops: count the body
            holds = np.r_[c.start[1:] < c.end[:-1], False]
            inside = self._in(c, self.window) & ~holds
            dur = (c.end - c.start) * 1e-9
            for k, d, ok in zip(c.kind, dur, inside):
                if ok:
                    ops[k] = ops.get(k, 0.0) + float(d)
        device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in device_ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps(top)]}


def _chip(plane) -> Chip:
    lines = {line.name: line for line in plane.lines}
    mods = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in lines["XLA Modules"].events) if "XLA Modules" in lines else [])
    ops = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, op_kind(ev.name))
                  for ev in lines["XLA Ops"].events) if "XLA Ops" in lines else [])
    mod_start = np.array([m[0] for m in mods], float)
    mod_end = np.array([m[1] for m in mods], float)
    mod_name = [m[2] for m in mods]
    names = sorted(set(mod_name))
    index = {n: i for i, n in enumerate(names)}
    start = np.array([o[0] for o in ops], float)
    end = np.array([o[1] for o in ops], float)
    module = np.full(len(ops), -1, int)
    if len(mod_start):
        j = np.searchsorted(mod_start, start, side="right") - 1
        ok = (j >= 0) & (start < mod_end[np.maximum(j, 0)])
        module[ok] = [index[mod_name[i]] for i in j[ok]]
    return Chip(start, end, [o[2] for o in ops], module, names, mod_start, mod_end,
                mod_name)


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chips.append(_chip(plane))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    spans.sort(key=lambda s: s[1])
    windows = [(s, e) for n, s, e in spans if n == "bench.window"]
    if windows:
        window = windows[0]
    else:
        times = [t for c in chips for t in (c.start.min(initial=np.inf), c.end.max(initial=0))]
        window = (min(times), max(times)) if times else (0.0, 0.0)
    return Reduced(chips, spans, window)


def reduce_dir(trace_dir: str) -> Reduced:
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {files}")
    return reduce_file(files[0])
