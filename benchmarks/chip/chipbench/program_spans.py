"""The program's own spans (``repro.obs``) that ran inside a run's window.

The program takes its spans on the host's ``perf_counter``, the clock of
the window's marks. A program without ``repro.obs`` has no spans: the
readers built on this module then read nothing and return None.
"""
from __future__ import annotations

import importlib
from typing import List, Optional


def in_window(rec, name: str) -> Optional[List]:
    """The program's spans named ``name`` that started and ended inside
    the window; None where the program keeps no spans or the run has no
    window."""
    marks = rec.spans.marks
    if "window_start" not in marks or "window_end" not in marks:
        return None
    try:
        obs = importlib.import_module("repro.obs")
    except ImportError:
        return None
    return obs.records(name, since=marks["window_start"],
                       until=marks["window_end"])


def mean_seconds(rec, name: str) -> Optional[float]:
    spans = in_window(rec, name)
    return sum(s.seconds for s in spans) / len(spans) if spans else None
