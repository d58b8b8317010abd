"""Columnar execution engine: partitioned DataFrame, UDF registry, SQL shim.

Replaces the reference's Spark JVM data plane (SURVEY.md §1 L1, §2.3) with
an Arrow-native engine sized to this framework's workloads.
"""

# import_s of the start-up record: this package's first import, with what
# it pulls in (core/profiling.py; stdlib only, so it costs nothing itself)
from sparkdl_tpu.core import profiling as _profiling

_import_started = _profiling.import_begin()

from sparkdl_tpu.engine.dataframe import (  # noqa: E402
    DataFrame,
    EngineConfig,
    TaskFailure,
    sql,
    table,
)
from sparkdl_tpu.engine.supervisor import (  # noqa: E402
    PartitionSupervisor,
    SupervisorConfig,
    TaskAttempt,
)

_profiling.import_end(_import_started)

__all__ = ["DataFrame", "EngineConfig", "TaskFailure", "TaskAttempt",
           "PartitionSupervisor", "SupervisorConfig", "sql", "table"]
