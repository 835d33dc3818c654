from repro.shardlib.rules import (DEFAULT_RULES, axis_rules, batch_axes,
                                  current_mesh, current_rules, logical_spec,
                                  shd, tree_shardings)

__all__ = ["DEFAULT_RULES", "axis_rules", "batch_axes", "current_mesh",
           "current_rules", "logical_spec", "shd", "tree_shardings"]
