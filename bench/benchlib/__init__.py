"""The benchmark's own library: loading the cell, making inputs and
weights from the seed, driving the served path, reducing the profiler
trace and deciding ``correct``. Nothing here is imported by the program."""
