"""Secure aggregation (Bonawitz et al. 2017): pairwise masks over the integer
codes, removed again before the mean, so the decoded update is unchanged."""
biased = False


def roundtrip(x, key, arg, rest, opts):
    return rest(x, key)


def payload(n, arg, fused, opts):
    """The mask context that rides with each payload: a key, the client's
    ring index and the cohort size."""
    return 16, 0
