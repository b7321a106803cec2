"""90th percentile of the trainer's step time, in ms: the durations of
the program's `repro.train.step` spans over the window's steps 1.. (step
0 holds the step's trace), host work and the wait for the device
together; at about 155 steps it is the highest percentile with ten
steps beyond it. Read from `repro.obs` after the window. Moves
`train_tokens_per_s`."""
from bench import spans


def read(m):
    return spans.step_p90_ms(m.info["attempted"])
