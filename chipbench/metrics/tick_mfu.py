"""Required operations of the ticks in the traced window over the device
time they spanned, over the chip's bf16 peak (percent).  "Required" is the
least algorithm's work (chipbench/counts.py, or the configuration's
reference module where it counts its own).  Layer: tick on device."""
import counts


def read(run):
    devs = run.trace["devices"]
    block = run.config["serving"]["block_length"]
    per_tick = counts.traced_ticks(run)
    if not per_tick:
        return None
    flops = sum(counts.tick_flops(run.model, block, a, run.reference)
                for a in per_tick)
    share = []
    for d in devs.values():
        if d["ticks"] > 1 and d["tick_span_s"] > 0:
            mean = flops / len(per_tick)
            share.append(d["ticks"] * mean / d["tick_span_s"]
                         / run.peaks["bf16_flops_s"])
    return 100.0 * sum(share) / len(share) if share else None
