"""The chip benchmark of the FPCA serving path (see ``bench/run.py``)."""
