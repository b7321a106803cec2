"""6 P tokens over wall time x chips x the bf16 peak, for the trainer
window: P is the configuration's analytic parameter count per client
with the tied embedding once, tokens are those the window's steps
consumed over all clients. Recomputation does not count. Moves
`train_tokens_per_s`."""


def read(m):
    flops = m.counts.get("model_flops")
    if not flops or m.window_s <= 0:
        return None
    return 100.0 * flops / (m.window_s * m.chips * m.peaks.bf16_flops)
