"""From a profiler trace to numbers: ``xplane`` reads the file,
``reducers`` holds the reductions that the per-layer metric files name."""
