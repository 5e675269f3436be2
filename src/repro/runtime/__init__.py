"""Runtime services: fault tolerance, host spans for the profiler."""
