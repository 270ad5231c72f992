"""Share (%) of the kernel points timed in the run whose compile JAX
served from its persistent compilation cache (its monitoring events)."""


def read(run):
    timed = [p for p in run.points if not p.refused and p.backend_compiles]
    if not timed:
        return None
    return 100.0 * sum(p.cache_hits >= p.backend_compiles for p in timed) / len(timed)
