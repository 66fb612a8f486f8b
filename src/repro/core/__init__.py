"""The paper's primary contribution: distributed blocked matmul.

    from repro.core import dbcsr
    from repro.core.multiply import distributed_matmul
"""
from .blocking import BlockLayout, GridSpec
from .multiply import distributed_matmul
from .cannon import cannon_matmul, build_cannon_schedule
from .cannon25d import cannon25d_matmul, build_cannon25d_schedule
from .tall_skinny import (tall_skinny_matmul, build_ts_schedule,
                          classify_shape, ts_classify_ratio, DEFAULT_TS_RATIO)
from .summa import (summa_matmul, build_summa_schedule,
                    build_summa_gather_schedule)
from .schedule import (Schedule, execute_schedule, DEFAULT_PIPELINE_DEPTH,
                       resolve_pipeline_depth)
from .densify import densify, undensify, to_blocks, from_blocks
from .engine import (ExecutorPlan, build_executor_plan, execute_plan,
                     stack_executor)
from .stacks import build_stacks, pad_plans, StackPlan, STACK_SIZE

__all__ = [
    "BlockLayout", "GridSpec", "distributed_matmul", "cannon_matmul",
    "cannon25d_matmul", "tall_skinny_matmul", "classify_shape",
    "ts_classify_ratio", "DEFAULT_TS_RATIO",
    "summa_matmul", "densify", "undensify", "to_blocks", "from_blocks",
    "build_stacks", "pad_plans", "StackPlan", "STACK_SIZE",
    "ExecutorPlan", "build_executor_plan", "execute_plan", "stack_executor",
    "Schedule", "execute_schedule", "DEFAULT_PIPELINE_DEPTH",
    "resolve_pipeline_depth", "build_cannon_schedule",
    "build_cannon25d_schedule", "build_summa_schedule",
    "build_summa_gather_schedule", "build_ts_schedule",
]
