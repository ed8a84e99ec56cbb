"""Seconds the launcher spent compiling the train step (or loading it from
the persistent cache), as `Trainer.compile_s` reports them."""


def read(record: dict):
    return record["compile_s"]
