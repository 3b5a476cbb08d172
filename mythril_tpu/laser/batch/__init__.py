"""Batched concrete EVM interpreter (device-side)."""

from mythril_tpu.laser.batch.state import (  # noqa: F401
    CodeTable,
    StateBatch,
    Status,
    make_batch,
    make_code_table,
)
from mythril_tpu.laser.batch.step import step  # noqa: F401
from mythril_tpu.laser.batch.run import run  # noqa: F401
