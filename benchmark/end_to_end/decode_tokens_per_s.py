"""Output tokens emitted inside the window, by every request whether
it finished or not, over the window's seconds."""


def read(obs):
    end = obs["window_s"]
    tokens = sum(
        sum(1 for t in r["token_times"] if t <= end)
        for r in obs["serve"]["requests"]
    )
    return tokens / end
