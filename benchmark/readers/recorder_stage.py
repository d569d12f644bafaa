"""A percentile of the flight recorder's stages, over the frames whose span
began in the window: ``args.stages`` are added up per frame (a frame that
lacks one of them is left out), ``args.percentile`` picks the statistic."""

from ..metrics import percentile


def read(run, args):
    stages = args["stages"]
    vals = []
    for tr in run.spans:
        if all(s in tr.spans for s in stages):
            vals.append(sum((tr.spans[s][1] - tr.spans[s][0]) * 1000.0
                            for s in stages))
    return percentile(vals, float(args.get("percentile", 50))) if vals else None
