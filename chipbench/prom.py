"""Counters from the ``/metrics`` scrapes the client takes when the window
opens and closes (Prometheus text exposition)."""


def series(text: str, name: str) -> float:
    """Sum of a series over its labels."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in "{ ":
            total += float(line.rsplit(" ", 1)[1])
    return total


def delta(scrapes: dict, name: str) -> float:
    return (series(scrapes["close"][1], name)
            - series(scrapes["open"][1], name))
