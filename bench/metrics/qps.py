"""Queries whose result arrived inside the window, per second from the
window's opening to the last of those results.

Dividing by the whole window instead would count a closed loop's answers
in whole steps: with 4 answers every 3.7 s in a 47 s window, a step up to
4% slower still ends 12 steps inside it and reads the same.  Up to the
last answer, the time moves with every step."""

from traffic import completed_in


def read(run):
    n = completed_in(run.records, run.t1)
    if not n:
        return None
    last = max(r.done for r in run.records
               if r.done is not None and r.done <= run.t1)
    return n / (last - run.t0)
