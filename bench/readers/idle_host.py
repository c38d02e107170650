"""Dispatch and device: percent of the traced window in which no
operation ran on the device while the flusher was awake.

The device's idle intervals, less the union of the flusher's ``wait``
spans (asleep with nothing in flight and nothing due), over the window.
What is left is idle time the host path owns: taking, re-planning,
dispatching, collecting, compiling.  A program whose flusher loop records
no ``take`` spans cannot tell its waits from its work, so nothing is
read there.
"""


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def read(ctx):
    tr = ctx["trace"]
    spans = ctx["spans"] or ()
    if tr is None or not tr.ops or tr.window_ns <= 0:
        return None
    if not any(s.name == "take" for s in spans):
        return None
    lo, hi = tr.window
    waits = _merged((max(s.start_us * 1e3, lo), min(s.end_us * 1e3, hi))
                    for s in spans
                    if s.name == "wait" and s.end_us is not None
                    and s.end_us * 1e3 > lo and s.start_us * 1e3 < hi)
    idle = 0.0
    j = 0
    for g0, g1 in tr.idle_gaps():
        idle += g1 - g0
        while j < len(waits) and waits[j][1] <= g0:
            j += 1
        k = j
        while k < len(waits) and waits[k][0] < g1:
            idle -= min(g1, waits[k][1]) - max(g0, waits[k][0])
            k += 1
    return 100.0 * idle / tr.window_ns
