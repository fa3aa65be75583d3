"""Scheduling and dispatch: median, over the window's completed jobs, of
the time from the wait that covered a job's last slice to the return of
its ``drain()``: how long the blocking drain holds a finished job."""
import statistics


def read(rec):
    held = [d["returned_at"] - j.done for d in rec.drains
            for j in d.get("jobs", ())]
    return 1e3 * statistics.median(held) if held else None
