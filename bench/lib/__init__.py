"""The benchmark harness: loaders, traffic, checkpoint, client loop, trace
reduction and the correctness comparison (see ``bench/run.py``)."""
