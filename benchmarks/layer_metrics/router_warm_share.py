"""DP router: share of turns >= 2 that found the thread's previous turn
cached (cached_tokens >= previous prompt_tokens less one page), client side."""
import e2e


def read(ctx):
    share = e2e.warm_turns(ctx["log"],
                           ctx["cell"].config["serving"]["page_size"])
    return None if share is None else 100.0 * share
