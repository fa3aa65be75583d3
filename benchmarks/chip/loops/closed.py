"""Closed loop: each tenant has ``clients`` clients; a client sends its
next job when its last one completes (at the return of the drain that
ran its last slice). Latency counts from the job's admission. Job sizes
come in the same order for every seed."""
import traffic


def drive(win, mix: dict, seed: int):
    sizes = {t["name"]: traffic.size_stream(t["slices"], "sizes/" + t["name"])
             for t in mix["tenants"]}
    for t in mix["tenants"]:
        for _ in range(t["clients"]):
            win.admit(t["name"], next(sizes[t["name"]]))
    while True:
        done = win.drain()
        if win.now() >= win.deadline:
            return
        for job in done:
            win.admit(job.tenant, next(sizes[job.tenant]))
