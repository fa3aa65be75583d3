"""Admission: median, over the window's completed jobs, of the time from
a job's admission (``SharedPodServer.admit``) to the enqueue of its first
slice, from the job records each ``drain()`` returns."""
import statistics


def read(rec):
    jobs = [j for d in rec.drains for j in d.get("jobs", ())]
    if not jobs:
        return None
    return 1e3 * statistics.median(j.first_dispatch - j.admitted_at
                                   for j in jobs)
