"""From a profiler trace to per-layer numbers: the reduction of an
``.xplane.pb`` (busy union, idle share, kernel sums, gap attribution),
the table of published peaks, and the functions that compute a
kernel's operations and bytes from its shapes."""
