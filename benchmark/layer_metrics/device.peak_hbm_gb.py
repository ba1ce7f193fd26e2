"""Peak device memory on the fullest chip, in GB (1e9 bytes), by the chip's
own account: ``memory_stats()`` after the window, ``peak_bytes_in_use``
(live buffers) plus ``peak_bytes_reserved`` (the arena reserved for
programs' temporaries, which stays reserved between steps). One field alone
is not the answer: on the v5e the first read 4.7 GB and the second 7.0 GB
under a 4.1 GB state, and ``bytes_limit`` less the largest free block
agreed with their sum. Headroom here is batch."""


def read(record):
    peak = record["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
