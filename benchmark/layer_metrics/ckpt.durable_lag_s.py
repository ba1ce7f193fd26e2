"""Seconds by which a save's durability trails the loop: from the end of
its ``train::report_sharded`` (the loop goes on) to the end of the
``ckpt::commit`` that carries its ``seq`` (the manifest is written: the
checkpoint exists), median over the window's saves whose commit was
recorded, wherever it ended. Read only of saves written behind the loop,
which open a ``ckpt::drain_wait``: a program that writes inside the stall
reads None."""

import harness
import program_spans


def read(record):
    from ray_tpu.util import tracing
    committed = {s.attributes.get("seq"): s.perf_start + s.duration
                 for s in tracing.get_spans()
                 if s.name == "ckpt::commit" and s.duration is not None
                 and getattr(s, "perf_start", 0.0)}
    return harness.median(
        committed[save.attributes.get("seq")]
        - (save.perf_start + save.duration)
        for save, children in program_spans.saves(record)
        if save.attributes.get("seq") in committed
        and any(s.name == "ckpt::drain_wait" for s in children))
