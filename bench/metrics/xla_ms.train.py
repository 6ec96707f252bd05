"""Device ms per PPO iteration of every op of the train program that is
neither a Pallas call nor a collective (noise pre-draw, GAE, minibatch
epochs), per chip."""


def read(run):
    t = run.get("trace")
    if run["kind"] != "train" or not t:
        return None
    return t["other_s"] / run["iterations"] * 1e3
