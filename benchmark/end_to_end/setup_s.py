"""Process start to the first timed step: ``ray_tpu.init``, trainer start,
state made on the device from the seed, compile or cache load, the
reference check, one warm-up step."""


def read(record):
    return record["setup"]["setup_s"]
