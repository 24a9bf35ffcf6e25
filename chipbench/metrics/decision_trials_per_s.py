"""Campaign trials planned per second over whole decisions: seeds times
candidates of every request answered in the window, over the window's
seconds (from the first request's issue to the last answer), each request
a planning child from its spawn to its exit. The child's start and exit
take most of a decision, and the host's stalls in them make this figure
too unsteady for an end-to-end bound; set-up holds one whole decision."""


def read(run):
    if not run["trials"]:
        return None
    return sum(run["trials"]) / run["window_s"]
