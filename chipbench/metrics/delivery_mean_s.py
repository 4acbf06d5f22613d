"""Mean wait from an engine commit to its SSE write on the server's event
loop, over the commits streamed in the window: ``dllm_delivery_seconds``
sum over count between the scrapes at the window's open and close
(seconds).  Layer: frontend + router."""
from prom import delta


def read(run):
    n = delta(run.scrapes, "dllm_delivery_seconds_count")
    if not n:
        return None
    return delta(run.scrapes, "dllm_delivery_seconds_sum") / n
