"""Engine scheduler: the share of the window the engine loop's thread was
off the CPU while it meant to work: over its `admit` and `join` stretches
and its `prefill_chunk` / `decode` dispatch brackets (stepscope records), the
stretch's wall time less the thread's own CPU time (`cpu_us`,
`time.thread_time_ns` at both ends), cut to the window. In those stretches
the thread neither waits on purpose nor is idle, so the difference is time
it slept in line for the interpreter lock or stood runnable with its core
taken (which of the two the records' `runq_us` says, on a host that keeps
`/proc/<pid>/task/<tid>/schedstat`; the chip's does not). A program whose
records carry no `cpu_us` reports nothing."""

from benchmarks.host_spans import loop_blocked_share


def read(obs):
    return loop_blocked_share(obs)
