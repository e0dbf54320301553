"""slot_occupancy: mean share of the batch's slots that decoded in a
window step (requests that got tokens from its decode chunk / batch), %.
Program counters (tokens the engine delivered); moves output_tok_s."""


def read(rec):
    steps = rec.window_steps()
    if not steps:
        return None
    batch = rec.serve["batch"]
    return 100.0 * sum(len(s.tokens) for s in steps) / (len(steps) * batch)
