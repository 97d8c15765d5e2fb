"""The benchmark: client-side serving cells run on the chip (``run.py``)."""
