"""Stand-in cluster workers for tests/cluster/test_boot.py. Module-level so
the spawn context can pickle them by import path; each takes
``_worker_main``'s arguments."""

import time


def silent(*_args) -> None:
    """Never reports a boot outcome: what a process stuck waiting for a
    device it cannot have looks like to the router."""
    time.sleep(3600)


def split_landing(worker_id, tasks, conn, *_rest) -> None:
    """Boots, but worker 0 lands on the TPU and the others on the CPU: what
    JAX's silent fallback does when no platform is configured and only one
    process can have the chip. Exits on the poison pill."""
    conn.send(("booted", worker_id, "tpu" if worker_id == 0 else "cpu"))
    while tasks.get() is not None:
        pass
    conn.close()
