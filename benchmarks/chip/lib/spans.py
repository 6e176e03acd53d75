"""Host spans nested by containment, and device idle time split over them.

The readers get `Trace.host_spans`: `(start, end, name)` of every span
that STARTS inside the traced slice — the benchmark's own
(`bench.traced`, `sched.step`, ...) and, since the program annotates
its scheduler, the program's (`engine.step` and the phases beneath it),
all from the one thread that drives the engine, so they nest. A span
is the child of the innermost span that contains it. A child whose
parent began before the slice has no parent here — an orphan: the
readers look only beneath whole `engine.step` spans, so it is skipped.

`xplane.Trace.idle_gaps` files a gap under the span open when the gap
BEGAN; `split_idle` here splits each gap over the spans that OVERLAP
it, innermost first.
"""

from __future__ import annotations

import bisect

EPS = 1e-9  # a nanosecond: starts and lengths are whole nanoseconds


class Span(object):
    __slots__ = ("start", "end", "name", "children")

    def __init__(self, start, end, name):
        self.start, self.end, self.name = start, end, name
        self.children = []

    @property
    def seconds(self):
        return self.end - self.start

    def walk(self):
        """This span and everything beneath it, parents first."""
        yield self
        for c in self.children:
            for n in c.walk():
                yield n

    def inside(self, name):
        """Seconds of the spans of that name beneath this one (a span
        of that name nested in another of that name counts once)."""
        total = 0.0
        for c in self.children:
            total += c.seconds if c.name == name else c.inside(name)
        return total

    def self_segments(self):
        """The (start, end) stretches of this span no child covers."""
        at = self.start
        for c in self.children:
            if c.start > at:
                yield at, c.start
            at = max(at, c.end)
        if self.end > at:
            yield at, self.end

    @property
    def self_seconds(self):
        return sum(e - s for s, e in self.self_segments())


def nest(host_spans):
    """(start, end, name) spans -> the top-level `Span`s, each holding
    its children in order of start. Two spans that overlap without one
    containing the other (threads; not seen here) become siblings."""
    top, stack = [], []
    for s, e, name in sorted(host_spans, key=lambda x: (x[0], -x[1])):
        node = Span(s, e, name)
        while stack and stack[-1].end + EPS < e:
            stack.pop()  # ended before this one does: not its parent
        (stack[-1].children if stack else top).append(node)
        stack.append(node)
    return top


def find(forest, name):
    """Every span of that name, outermost only, in order of start."""
    found = []
    for root in forest:
        if root.name == name:
            found.append(root)
        else:
            found.extend(find(root.children, name))
    return found


def idle_intervals(ops, lo, hi):
    """The stretches of [lo, hi] in which no operation of `ops`
    ((start, duration, name), any order) ran."""
    gaps, at = [], lo
    for s, d, _ in sorted(ops):
        if s >= hi:
            break
        if s > at:
            gaps.append((at, s))
        at = max(at, s + d)
    if hi > at:
        gaps.append((at, hi))
    return gaps


class _Idle(object):
    """Idle seconds before an instant, over sorted disjoint gaps."""

    def __init__(self, gaps):
        self.starts = [s for s, _ in gaps]
        self.ends = [e for _, e in gaps]
        self.cum = [0.0]
        for s, e in gaps:
            self.cum.append(self.cum[-1] + (e - s))

    def before(self, t):
        i = bisect.bisect_right(self.starts, t)
        total = self.cum[i]
        if i and self.ends[i - 1] > t:
            total -= self.ends[i - 1] - t
        return total

    def between(self, s, e):
        return self.before(e) - self.before(s)

    def edges(self, s, e):
        """Of the idle time inside [s, e]: the part in a gap that was
        already open at `s` (the device had not started yet) and the
        part in a gap still open at `e` (it had finished)."""
        i = bisect.bisect_right(self.starts, s) - 1
        head = max(0.0, min(self.ends[i], e) - s) if i >= 0 else 0.0
        j = bisect.bisect_left(self.starts, e) - 1
        tail = (e - max(self.starts[j], s)
                if j >= 0 and j != i and self.ends[j] >= e else 0.0)
        return head, tail


def split_idle(gaps, forest, root, wait=None):
    """Idle seconds by the innermost span that overlaps them.

    -> (inside, outside): `inside` by span name for the time beneath a
    whole `root` span (the program's scheduler), `outside` by span name
    for the rest (the harness's own spans; "no_span" where none was
    open). The idle time of the `wait` spans (the host blocked on the
    device) is filed apart where the device was idle as the wait began
    (`<wait>:before_start`: the program had not started) and where it
    was idle as the wait ended (`<wait>:after_end`: the result was on
    its way back)."""
    inside, outside = {}, {}
    idle = _Idle(gaps)

    def file(into, name, t):
        if t:
            into[name] = into.get(name, 0.0) + t

    def visit(node, into):
        if node.name == root:
            into = inside
        t = sum(idle.between(s, e) for s, e in node.self_segments())
        if node.name == wait and not node.children:
            head, tail = idle.edges(node.start, node.end)
            file(into, wait + ":before_start", head)
            file(into, wait + ":after_end", tail)
            t -= head + tail
        file(into, node.name, t)
        for c in node.children:
            visit(c, into)

    for top in forest:
        visit(top, outside)
    covered = sum(inside.values()) + sum(outside.values())
    bare = idle.cum[-1] - covered
    if bare > EPS:
        outside["no_span"] = bare
    return inside, outside
