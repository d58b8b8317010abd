"""Training subsystem — pjit train steps, optax, Orbax resume, TPURunner.

Parity map (SURVEY.md §3.3, §3.5, §5.3–§5.5): the reference trained
driver-locally with keras ``model.fit`` after collecting features, and its
distributed story was HorovodRunner (Spark barrier mode + MPI + NCCL ring
all-reduce). Here:

- the train step is ONE jitted XLA program over a device mesh — batch
  sharded on ``data``, params replicated; XLA emits the gradient
  all-reduce over ICI/DCN (no NCCL, no hand-written collectives);
- checkpoint/resume is Orbax on ``{params, opt_state, step, rng,
  model_state}`` — the mid-training resume the reference lacked;
- ``TPURunner(np).run(train_fn)`` is the HorovodRunner-parity entry:
  gang semantics with restart-from-checkpoint on failure, and a fault
  injection hook to test it.
"""

# import_s of the start-up record: this package's first import, with what
# it pulls in (core/profiling.py; stdlib only, so it costs nothing itself)
from sparkdl_tpu.core import profiling as _profiling

_import_started = _profiling.import_begin()

from sparkdl_tpu.train.checkpoint import CheckpointManager  # noqa: E402
from sparkdl_tpu.train.metrics import MetricsLogger  # noqa: E402
from sparkdl_tpu.train.optimizers import make_loss, make_optimizer  # noqa: E402
from sparkdl_tpu.train.runner import TPURunner  # noqa: E402
from sparkdl_tpu.train.trainer import Trainer, TrainState  # noqa: E402

_profiling.import_end(_import_started)

__all__ = [
    "CheckpointManager",
    "MetricsLogger",
    "TPURunner",
    "Trainer",
    "TrainState",
    "make_loss",
    "make_optimizer",
]
