"""Model FLOP utilization of the window, %: output tokens times the
backbone's forward FLOPs per token at each token's context
(``counts/backbone_forward.py``), over seconds x chips x peak bf16 FLOP/s."""


def read(run):
    fwd = run.counts("backbone_forward")
    w = run.window
    flops = sum(fwd.tokens(run.conf, ctx, n) for s in w.steps
                if s.end <= w.end for ctx, n in s.work)
    return 100.0 * flops / ((w.end - w.start) * run.chips
                            * run.peaks["bf16_flops_per_s"])
