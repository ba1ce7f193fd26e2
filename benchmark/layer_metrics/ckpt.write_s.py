"""Mean seconds of a save's write as the program counts it: its histogram
``ray_tpu_train_ckpt_save_seconds`` (the shard's ``write_s``, backend write
and fsync, plus the manifest commit) over the saves of the window."""


def read(record):
    seconds = record["save_seconds"]
    if not seconds["count"]:
        return None
    return seconds["sum"] / seconds["count"]
