"""Roofline share of the Pallas `gossip_mix` kernel in the trainer: the
least time of the work that the window's transmit masks make necessary
(each transmitting client's f32 update row read once, each receiving
row written once; 2 P FLOPs per link) over the kernel's summed device
time. Counts come from the seed-determined transmit masks of the
window's steps. Moves `train_tokens_per_s`."""
from bench import trace, work

KERNEL = trace.pallas_call(2)


def read(m):
    t = trace.kernel_s(m.summary, KERNEL)
    if t <= 0 or not m.counts.get("mix_bytes"):
        return None
    least = work.least_time_s(m.counts["mix_flops"], m.counts["mix_bytes"],
                              m.peaks.bf16_flops, m.peaks.hbm_bytes_per_s)
    return 100.0 * least / t
