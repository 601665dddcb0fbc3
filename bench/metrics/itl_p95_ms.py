"""95th percentile of the gap between consecutive tokens of one request,
over every gap of every request inside the window.  Host clock."""
from bench import driver


def read(run):
    return driver.percentile(driver.token_gaps_ms(run.window), 95)
