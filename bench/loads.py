"""The one traffic generator that every mix file in ``bench/traffic/`` drives.

A mix file names its loop.  The one loop there is, ``closed``: one client
issues the cell's query back to back.  The window runs from the first
query's issue to the completion of the query that is in flight when the
window's seconds have passed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, List, Optional


@dataclasses.dataclass
class Record:
    due: float                    # issue time
    done: Optional[float] = None
    error: Optional[str] = None


def closed_loop(issue: Callable[[], Any], seconds: float,
                annotate: Callable[[str], Any] = lambda _:
                contextlib.nullcontext()) -> List[Record]:
    """Issue back to back until ``seconds`` have passed since the first
    issue; the query in flight then completes and closes the window."""
    records: List[Record] = []
    while True:
        rec = Record(due=time.perf_counter())
        try:
            with annotate("bench.query"):
                issue()
        except Exception as err:  # a failed query counts in `failed`
            rec.error = repr(err)
        rec.done = time.perf_counter()
        records.append(rec)
        if rec.done - records[0].due >= seconds:
            return records
