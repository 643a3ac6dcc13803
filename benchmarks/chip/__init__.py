"""On-chip benchmark of CURing and paged serving (see ``run.py``)."""
