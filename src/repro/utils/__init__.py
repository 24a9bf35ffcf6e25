from repro.utils.tree import tree_bytes, tree_hash, tree_equal, split_params
