"""The host symbolic-state lock.

Every piece of host-side symbolic machinery is process-global by
design (reference parity: mythril/support/support_utils.py documents
its singletons as explicitly not thread-safe): the hash-consed term
arena, the incremental CDCL blast session, the model cache,
`PhaseProfile` and the `Args` flags. A device wave, by contrast,
touches none of it — `sym_run` plus its numpy readbacks are pure
jax/numpy (laser/batch/arena.py defers term construction until a flip
is actually decoded).

So the lock guards host symbolic work done in this process, and only
that. The overlapped corpus mode takes it on both sides: a prepass
thread may run device waves freely while the main thread analyzes
contracts, provided BOTH take this lock around any host symbolic work
(flip decode + solve bursts on one side, whole per-contract analyses
on the other). The serve engine's host threads take it around an
in-process walk, which they do only where walker processes cannot be
had: `host_workers` 1, or a single CPU left beside the engine. With
two or more walkers (service/walkers.py) every walk runs in a process
of its own, with singletons of its own, and no walk takes the lock.

Coarse on purpose: within one process the win is device-vs-host
overlap, and walks side by side come from processes, not threads.
"""

from __future__ import annotations

import threading

HOST_SYMBOLIC_LOCK = threading.Lock()
