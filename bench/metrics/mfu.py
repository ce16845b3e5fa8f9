"""Model step: the window's useful model work as a share of the chip's
bf16 peak: effective model evals (the engine's per-sample count, wasted
speculative and masked lanes left out) resolved within the window, times
the DiT's operations per sample-eval, over the window and the peak."""
from bench import flops


def read(run):
    evals = sum(r[1] for r in run.resolves_in_window())
    if not evals:
        return None
    ops = evals * flops.dit_sample_eval_flops(run.config)
    return 100.0 * ops / run.seconds / run.peak["bf16_flops_per_s"]
