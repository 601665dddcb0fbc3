"""75th percentile of first token minus due time over the fresh requests
(first turns) due in the window; one still waiting at the close counts at
its age then.  Host clock."""
from bench import driver


def read(run):
    return driver.percentile(driver.first_token_ms(run.window, "fresh"), 75)
