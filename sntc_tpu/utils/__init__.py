from sntc_tpu.utils.compile_cache import enable_persistent_cache
from sntc_tpu.utils.logging import MetricsLogger
from sntc_tpu.utils.profiling import (
    TransferLedger,
    ledger_scope,
    transfer_ledger,
)

__all__ = [
    "MetricsLogger",
    "TransferLedger",
    "transfer_ledger",
    "ledger_scope",
    "enable_persistent_cache",
]
