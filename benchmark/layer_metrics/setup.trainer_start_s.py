"""``fit()`` to the train function's first statement, as the program's own
stages say it: ``worker_group`` (placement, actors, chip reservation) +
``backend`` (the ``setup_env`` barrier, ``jax.distributed.initialize`` where
there is a gang) + ``loop_start`` (dataset shards, session, launch; the
mean over the ranks of this process, which is rank 0's where there is one
worker). The runner's ``trainer_start_s`` times the same from outside."""

import program_setup


def read(record):
    parts = [program_setup.stage_seconds(stage)
             for stage in ("worker_group", "backend", "loop_start")]
    if None in parts:
        return None
    ranks = program_setup.count(program_setup.SETUP, stage="loop_start")
    return parts[0] + parts[1] + parts[2] / ranks
