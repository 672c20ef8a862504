"""Step programs: % of the capture's device busy time in ops that carry no
`tf_op` at all (compiler-inserted copies and the like): the account's own
blind spot.  scope_reduce.py's `top_unattributed` names them."""
import scope_reduce


def read(ctx):
    return scope_reduce.share(scope_reduce.of_ctx(ctx),
                              (scope_reduce.UNSCOPED,))
