"""Model FLOP utilization of serving, in percent: the operations that the
prompts and tokens served in the window need (counted from shapes in
counts/<config>.py) over the window times the chip's peak bf16 rate from
peaks.json.  Work the program does beyond that (padding, attention over
empty cache rows) does not count.  Layer: whole serving step."""


def read(r):
    if not r.served_flops:
        return None
    peak = r.peaks["bf16_flops_per_s"] * r.chips
    return 100.0 * r.served_flops / (r.window.window_s * peak)
