"""Host time per step spent building the batch: the harness's span around
each `next()` of the trainer's feed (data/corpus.py, data/sharding.py),
summed over the untraced window of a traced run, over its steps."""


def read(record: dict):
    w = record["window"]
    return 1e3 * w["input_s"] / w["steps"]
