"""Mean share of the batch slots that held a request, over the window's
ticks: tokens committed over ticks x slots x tokens each slot commits per
tick, between the scrapes at the window's open and close (percent).  Layer:
scheduler + engine loop."""
from prom import delta


def read(run):
    ticks = delta(run.scrapes, "dllm_ticks_total")
    if not ticks:
        return None
    s = run.config["serving"]
    per_tick = s["block_length"] / s["steps_per_block"]
    return 100.0 * delta(run.scrapes, "dllm_tokens_committed_total") / (
        ticks * s["num_slots"] * per_tick)
