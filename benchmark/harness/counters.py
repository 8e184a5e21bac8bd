"""The program's counters, read from its Prometheus exposition text."""

from __future__ import annotations


def snapshot() -> str:
    from drand_tpu import metrics

    return metrics.render().decode()


def metric_sum(text: str, name: str, **labels) -> float:
    """Sum of the samples of ``name`` whose labels include ``labels``."""
    want = [f'{k}="{v}"' for k, v in labels.items()]
    total = 0.0
    for line in text.splitlines():
        if line.startswith("#") or not line.startswith(name):
            continue
        head = line.split(" ")[0]
        if (head == name or head.startswith(name + "{")) and all(
                w in head for w in want):
            total += float(line.rsplit(" ", 1)[1])
    return total


def delta(before: str, after: str, name: str, **labels) -> float:
    return metric_sum(after, name, **labels) - metric_sum(before, name,
                                                          **labels)
