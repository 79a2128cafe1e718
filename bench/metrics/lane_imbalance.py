"""Most tokens committed in the window by one lane over the mean of the
lanes (1 is even); nothing to read with one lane."""


def read(rec):
    lanes = rec["lane_tokens"]
    if len(lanes) < 2 or not sum(lanes):
        return None
    return max(lanes) / (sum(lanes) / len(lanes))
