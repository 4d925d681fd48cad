"""KV manager: pool pages held (the engine's ``kv_free_pages`` gauge
after each decode step) over the pool's pages, averaged over the
window's steps, %."""


def read(run):
    free = [s.gauges["kv_free_pages"] for s in run.window_steps
            if "kv_free_pages" in s.gauges]
    if not free:
        return None
    return 100.0 * (1.0 - sum(free) / (len(free) * run.total_pages))
