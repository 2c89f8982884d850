from repro.utils.trees import tree_bytes, tree_size

__all__ = ["tree_bytes", "tree_size"]
