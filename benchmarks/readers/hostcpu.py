"""The share of the host's processor time that the run's own processes
took between the window's two ends: every server's and the load
generator's `time.process_time()` (all threads), which `run.py` asks for
at either end, over the window's seconds times the host's cores. Where
several servers share a host it is the resource they share. (`/proc/stat`
would count every process of the machine, but reads 0 on the chip's
sandboxed machine.)
"""


def read(spec: dict, ctx: dict):
    before, after = ctx.get("cpu_before"), ctx.get("cpu_after")
    window, cores = (ctx.get("window") or {}).get("seconds"), ctx.get("cores")
    if before is None or after is None or not window or not cores:
        return None
    return (sum(after) - sum(before)) / (window * cores) * float(spec.get("scale", 1.0))
