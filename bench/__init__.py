"""The chip benchmark of the DBCSR reproduction (see ``bench/run.py``)."""
