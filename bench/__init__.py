"""The chip benchmark of the EdgeLoRA serving engine (see ``run.py``)."""
