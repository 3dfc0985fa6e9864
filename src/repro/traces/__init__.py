from .generators import (SCAN_HEAVY_MIX, SESSION_ACTIVATE, SESSION_APPEND,
                         VM_ADDR_STRIDE,
                         SESSION_END, SESSION_NEW, SPECS, SessionSpec,
                         SessionTrace, WorkloadSpec, generate,
                         generate_sessions, generate_to_store, make,
                         make_store, names)
from .store import TraceStore, parse_blktrace, parse_msr_csv
from .stream import StreamingTraceSource, StreamWindow, window_source

__all__ = [
    "SCAN_HEAVY_MIX", "SPECS", "WorkloadSpec", "generate",
    "generate_to_store", "make", "make_store", "names", "VM_ADDR_STRIDE",
    "SESSION_NEW", "SESSION_ACTIVATE", "SESSION_APPEND", "SESSION_END",
    "SessionSpec", "SessionTrace", "generate_sessions",
    "TraceStore", "parse_blktrace", "parse_msr_csv",
    "StreamingTraceSource", "StreamWindow", "window_source",
]
