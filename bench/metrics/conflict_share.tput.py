"""conflict_share.tput: batch loop -- optimistic binds that failed bind-time
re-validation (the daemon's ``conflicts`` counter) / bind attempts (commit
calls) in the window."""


def read(run):
    attempts = run["commit_attempts"]
    return run["window_conflicts"] / attempts if attempts else None
