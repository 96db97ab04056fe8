"""``collective_bytes_per_iter``: the logical payload of every collective of
one boosting iteration, as the data-parallel chunk program files it at trace
time (``collective/bytes_per_tree`` times ``collective/trees_per_iter``:
each seam's operand bytes, from its traced shapes and dtypes, times its
executions a tree).  Nothing on a program without the counters."""


def read(state):
    per_tree = state.counters.get("collective/bytes_per_tree")
    trees = state.counters.get("collective/trees_per_iter")
    if per_tree is None or not trees:
        return None
    return per_tree * trees
