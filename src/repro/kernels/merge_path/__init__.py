from . import ops, ref
from .kernel import BLOCK, merge_path_call

__all__ = ["BLOCK", "merge_path_call", "ops", "ref"]
