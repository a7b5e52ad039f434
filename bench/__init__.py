"""The chip benchmark of the MemorySim reproduction (see ``run_cell.py``)."""
