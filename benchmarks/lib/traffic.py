"""The one traffic generator. A job file under ``benchmarks/jobs/`` holds
the parameters; nothing here knows a job's name.

``closed_loop_training``: a pool of ``pool_batches`` distinct global batches
of ``seq_len + 1`` token ids each (inputs are [:, :-1], labels [:, 1:]),
drawn from ``--seed``, placed on the device during set-up and cycled by the
loop. Every seed gives the same sizes; only the ids differ. No input
pipeline is modelled: the program has no loader to measure.
"""

from __future__ import annotations

import numpy as np

KINDS = ("closed_loop_training",)
TOKEN_DISTRIBUTIONS = ("uniform",)


def validate_job(job: dict) -> None:
    if job.get("kind") not in KINDS:
        raise ValueError(f"job kind {job.get('kind')!r} not in {KINDS}")
    if job.get("tokens") not in TOKEN_DISTRIBUTIONS:
        raise ValueError(f"job tokens {job.get('tokens')!r} not in "
                         f"{TOKEN_DISTRIBUTIONS}")
    for key in ("seq_len", "pool_batches"):
        if not (isinstance(job.get(key), int) and job[key] > 0):
            raise ValueError(f"job {key} must be a positive integer")


def token_pool(job: dict, *, seed: int, global_batch: int,
               vocab: int) -> np.ndarray:
    """int32 [pool_batches, global_batch, seq_len + 1]; the same for the
    program and for the reference, which calls this again from the seed."""
    validate_job(job)
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    return rng.integers(
        0, vocab, (job["pool_batches"], global_batch, job["seq_len"] + 1),
        dtype=np.int32)
