"""Host clock around ``ray_tpu.init()``."""


def read(record):
    return record["setup"]["init_s"]
