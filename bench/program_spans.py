"""The window's records in the program's span recorder (``repro.obs``).

A window makes exactly ``len(run.steps)`` calls to ``_decode_nodes`` and
``len(run.events)`` calls to ``_do_resize``, and the harness calls nothing
of the program after it.  So the window's records are the last
``len(run.steps)`` ``serve.step`` spans, the last ``len(run.events)``
``elastic.scale`` spans, and the descendants of both.  Where the recorder
dropped any of them, or the program has no recorder, the window has no
records (None), and the metrics that read them report nothing.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional


@dataclass
class Window:
    steps: List          # serve.step spans, in the order of run.steps
    events: List         # elastic.scale spans, in the order of run.events
    children: Dict[int, List]    # span id -> its child spans

    def below(self, root, name: str) -> List:
        """The descendants of ``root`` named ``name``."""
        out, todo = [], [root]
        while todo:
            for c in self.children.get(todo.pop().id, ()):
                if c.name == name:
                    out.append(c)
                todo.append(c)
        return out

    def event_ms(self, name: str) -> Optional[float]:
        """Mean per event of the summed duration of the ``name`` spans
        under each event; None where there is no event or no such span."""
        spans = [s for e in self.events for s in self.below(e, name)]
        if not self.events or not spans:
            return None
        return sum(s.dur_s for s in spans) / len(self.events) * 1e3

    def quiet_steps(self, run) -> List:
        """The serve.step spans of the steps that fired no event."""
        return [sp for st, sp in zip(run.steps, self.steps)
                if st.event is None]


def last(spans: List, name: str, n: int) -> List:
    hits = [s for s in spans if s.name == name]
    return hits[len(hits) - n:] if n else []


def window(run, recorder=None) -> Optional[Window]:
    """The window's records in ``recorder`` (default: the program's);
    None where the program has none or the recorder dropped some."""
    if recorder is None:
        try:
            from repro import obs
        except ImportError:
            return None
        recorder = obs.RECORDER
    spans = recorder.spans()
    steps = last(spans, "serve.step", len(run.steps))
    events = last(spans, "elastic.scale", len(run.events))
    if len(steps) != len(run.steps) or len(events) != len(run.events):
        return None
    roots = steps + events
    if roots and recorder.dropped_max_id >= min(r.id for r in roots):
        return None
    children: Dict[int, List] = {}
    for s in spans:
        children.setdefault(s.parent, []).append(s)
    return Window(steps, events, children)
