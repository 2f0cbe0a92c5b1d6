"""The benchmark of the served enumeration path (see ``run.py``)."""
