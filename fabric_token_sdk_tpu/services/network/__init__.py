from .ledger import Block, FinalityEvent, Network, TxStatus  # noqa: F401
from .orderer import (  # noqa: F401
    Backpressure, BlockPolicy, MessageTooLarge, Orderer, Submission,
)
from .pipeline import BusyClock, PipelinedBlockEngine  # noqa: F401
from .wal import WALError, WriteAheadLog  # noqa: F401
