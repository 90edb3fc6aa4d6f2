"""Share of the hop megakernel's roofline, in %: the least time the chip
could take for what the hop must read and compute (``roofline.py``) over
the summed device time of the hop's events."""
import roofline


def read(run):
    hops = run.trace.hop_ops() if run.trace and run.peaks else []
    if not hops:
        return None
    B, W, m, d, size = run.hop_shape()
    least = roofline.least_seconds(roofline.hop_flops(B, W, m, d),
                                   roofline.hop_bytes(B, W, m, d, size),
                                   run.peaks)
    return 100.0 * least * len(hops) / (sum(e[4] for e in hops) * 1e-9)
