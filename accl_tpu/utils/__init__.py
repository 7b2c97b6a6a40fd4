from .timing import Timer  # noqa: F401
from .logging import Log, LogLevel  # noqa: F401
from .platform import (  # noqa: F401
    DEVICE_PEAKS,
    device_peaks,
    use_compile_cache,
)
from .profiling import (  # noqa: F401
    annotate,
    annotated,
    device_memory_profile,
    device_scope,
    start_server,
    trace,
)
