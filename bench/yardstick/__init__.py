"""The benchmark's yardstick: what the program under test must not move.

Everything that decides a cell's numbers lives here, beside the files
that name the cells (``bench/configs``, ``bench/graphs``,
``bench/traffic``, ``bench/metrics``): the loading and checking of
those files, the open- and closed-loop drivers, the plain Dijkstra
reference, its controls and the comparison that decides ``correct``,
the percentile and window arithmetic, the reduction of a profiler
trace, the table of device peaks, and the least-work count behind a
kernel's roofline share.  From the program the benchmark takes only the
system under test (``build_served_index``, ``QueryEngine``,
``QueryServer``) and its spans and kernel names.
"""
