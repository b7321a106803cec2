"""Model FLOPs of the simulator window over wall time x chips x the bf16
peak: 6 P per trained sample of every gradient event that fired, 2 P per
evaluated sample, 2 K per delivered link (counts from the jobs' own
event draws, `entries/simulate.Protocol.counts`). The simulation runs
at HIGHEST, so against the bf16 peak the share is a floor. Moves
`sim_client_s_per_s`."""


def read(m):
    flops = m.counts.get("model_flops")
    if not flops or m.window_s <= 0:
        return None
    return 100.0 * flops / (m.window_s * m.chips * m.peaks.bf16_flops)
