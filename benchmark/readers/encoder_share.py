"""One of the encoder's own counts as a share, in %, of another, added up
over the displays: ``args.part`` over ``args.whole``, both keys of the
``stats()`` each display's encoder gave as the window closed
(``cavlc_low_tier_frames`` of ``cavlc_frames``: the P frames whose bits fit
the device pack's low output tier). None where an encoder lacks a key or
the whole is 0: a program that does not count has nothing to read."""


def read(run, args):
    stats = list(run.encoder_stats.values())
    if not stats or any(args["part"] not in st or args["whole"] not in st
                        for st in stats):
        return None
    whole = sum(st[args["whole"]] for st in stats)
    if not whole:
        return None
    return 100.0 * sum(st[args["part"]] for st in stats) / whole
