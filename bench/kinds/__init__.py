"""Cell drivers, one per configuration ``kind`` (see ``bench/harness.py``)."""
