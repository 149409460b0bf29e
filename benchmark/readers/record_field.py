"""A number the harness took itself (``setup_s``).  Spec: ``field``."""


def read(spec, record):
    return record.get(spec["field"])
