# The unified operator layer: one operator object (FaustOp), one
# factorization front door (factorize), cost-model backend dispatch.
from repro.api.dispatch import (
    DispatchReport,
    choose_backend,
    last_report,
)
from repro.api.factorize import (
    FactorizeInfo,
    FactorizeSpec,
    factorize,
)
from repro.api.operator import (
    FaustOp,
    ShardSpec,
    block_diag,
    hstack,
    vstack,
)

__all__ = [
    "DispatchReport",
    "FactorizeInfo",
    "FactorizeSpec",
    "FaustOp",
    "ShardSpec",
    "block_diag",
    "choose_backend",
    "factorize",
    "hstack",
    "last_report",
    "vstack",
]
