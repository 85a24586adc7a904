"""What every cell shares: look-up by name, the table of peaks, the compile
cache counter, the reduction from a profiler trace to numbers, and the
result line."""
