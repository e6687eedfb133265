"""One module or package a family: everything the harness needs that
depends on an architecture (``benchmark/family.py`` is the contract)."""
