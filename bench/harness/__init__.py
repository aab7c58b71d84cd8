"""Benchmark harness of the windowed KPCA service (see ``bench/run.py``).

Everything that decides a number lives here and imports nothing of the
program for its arithmetic: the deployment's data stream, the open-loop
schedule, the float64 reference and the comparison that decides
``correct``, the compile clock, the percentiles and the reduction of a
profiler trace.  The program is driven only through ``StreamBatch`` and
``IngestServeLoop``.
"""
