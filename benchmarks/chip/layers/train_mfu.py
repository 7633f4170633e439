"""The whole training step's share of the chip's peak: the training
operations of the samples actually trained in the window (3 forward passes
of the CNN per sample, counted from its shapes; padded users excluded),
over window seconds x chips x the bf16 peak."""


def read(run):
    flops = run.result["train_flops"]
    if flops <= 0:
        return None
    return 100.0 * flops / (run.result["window_s"] * run.chips
                            * run.peaks["bf16_flops_per_s"])
