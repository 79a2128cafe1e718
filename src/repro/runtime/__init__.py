from repro.runtime.errors import (  # noqa: F401
    ConfigError,
    DeadlineUnmeetable,
    DrainStalled,
    LedgerError,
    PoisonedRequest,
    SchedulerError,
)
from repro.runtime.request import Request, StreamCallback, pad_and_stack  # noqa: F401
from repro.runtime.multihost import (  # noqa: F401
    ShardedPageAllocator,
    ShardedStreamScheduler,
)
from repro.runtime.scheduler import (  # noqa: F401
    PageAllocator,
    SchedulerStats,
    StreamScheduler,
)
