"""`simulate`'s host entry per job, in ms: the median over the window's
jobs of the program's `repro.simulate.prepare` span (workload
resolution, context checks, `algo.init`), read from `repro.obs` after
the window. Moves `sim_client_s_per_s`."""
from bench import spans


def read(m):
    return spans.sim_host_entry_ms(m.info["attempted"])
