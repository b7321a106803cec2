"""The trainer's own host work per step, in ms: the median over the
window's steps 1.. of the self time of the program's spans
`repro.train.events` (transmit masks, Psi cap), `repro.train.batch`
(batch slice and placement) and `repro.train.dispatch` (the jitted
step's call), read from `repro.obs` after the window. Waiting for the
device (`repro.train.sync`) is not in it. Moves `train_tokens_per_s`."""
from bench import spans


def read(m):
    return spans.host_ms_per_step(m.info["attempted"])
