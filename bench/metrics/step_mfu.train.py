"""Model FLOP/s utilisation of the training window: the operations the
forward and backward passes need per token (``bench/flops``; recompute
not counted) times the tokens trained per second over the traced window's span,
over the chip's bf16 peak."""


def read(r):
    tokens, seconds = r.readings.get("tokens"), r.window_s
    if not tokens or not seconds:
        return None
    per_token = r.flops.train_flops_per_token(r.config["model"],
                                              r.readings["seq_len"])
    return 100.0 * tokens * per_token / seconds / r.peaks["bf16_flops"]
