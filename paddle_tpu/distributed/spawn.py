"""paddle.distributed.spawn (reference `distributed/spawn.py`).

In the single-controller TPU model one process drives all local chips, so
`spawn(fn, nprocs=-1)` simply runs `fn` once in-process — the caller owns
the chips.  Multi-host spawn launches one process per host via
multiprocessing when explicitly requested (each child must set
PADDLE_TRAINER_ID / COORDINATOR_ADDRESS).  There the CHILDREN own the
chips, one process at a time per chip: a parent that has already touched
a JAX backend holds the host's chips, and its children then fail or hang,
so call `spawn(nprocs>1)` before anything in the parent asks JAX for
devices.
"""
from __future__ import annotations

import os


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    if nprocs in (-1, 0, 1):
        func(*args)
        return None
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        env = {"PADDLE_TRAINER_ID": str(rank),
               "PADDLE_TRAINERS_NUM": str(nprocs)}
        p = ctx.Process(target=_entry, args=(func, args, env), daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
    return procs


def _entry(func, args, env):
    os.environ.update(env)
    func(*args)
