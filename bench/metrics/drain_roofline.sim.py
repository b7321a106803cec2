"""Roofline share of the Pallas gossip drain (`kernels/gossip`): the least
time of the work that the window's weights make necessary (each payload
row with a nonzero weight read once, each receiving row written once,
f32; 2 K FLOPs per delivered link) over the kernel's summed device time.
Counts come from the jobs' own event draws. Moves `sim_client_s_per_s`."""
from bench import trace, work

KERNEL = trace.pallas_call(3)


def read(m):
    t = trace.kernel_s(m.summary, KERNEL)
    if t <= 0 or not m.counts.get("drain_bytes"):
        return None
    least = work.least_time_s(m.counts["drain_flops"], m.counts["drain_bytes"],
                              m.peaks.bf16_flops, m.peaks.hbm_bytes_per_s)
    return 100.0 * least / t
