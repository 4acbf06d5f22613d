"""Mean wait from arrival to admission of the requests admitted in the
window: ``dllm_queue_wait_seconds`` sum over count between the scrapes at
the window's open and close (seconds).  Layer: scheduler + engine loop."""
from prom import delta


def read(run):
    n = delta(run.scrapes, "dllm_queue_wait_seconds_count")
    if not n:
        return None
    return delta(run.scrapes, "dllm_queue_wait_seconds_sum") / n
