from . import ops, ref
from .kernel import BLOCK, departure_tolerance, lindley_scan_call

__all__ = ["BLOCK", "departure_tolerance", "lindley_scan_call", "ops", "ref"]
