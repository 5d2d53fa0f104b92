"""Model FLOPs utilisation of the whole train step: model FLOPs per token
(``bench/flops.py``: forward and backward, nothing recomputed) times the
tokens per second of the traced window, over the chips' bf16 peak
(``bench/peaks.json``)."""


def read(run):
    peak = run.peak.get("bf16_flops_per_s")
    if not peak or run.steps == 0:
        return None
    return 100.0 * run.flops_per_token * run.tokens_per_s / (run.chips * peak)
