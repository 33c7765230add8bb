"""GitHub-style organisation in two namespaces: users in teams, teams
nested in teams (namespace ``team_namespace``), and repository roles
(pull, triage, push, admin; namespace ``repo_namespace``) granted to
teams or to collaborators directly. Shares of the tuples (``mix``): team
memberships, team nesting, and the rest grants on a uniformly drawn
repository role, ``grant_to_team`` of them to a team and the rest to a
user, topped up until the store holds exactly ``tuples`` distinct tuples.
Drawn with numpy from the seed.
"""

from __future__ import annotations

import numpy as np

from ..graph import Graph, Kind, fill, pick

ROLES = ("pull", "triage", "push", "admin")


def build(sizes: dict, rng) -> Graph:
    n = int(sizes["tuples"])
    users = Kind("user", 0, int(sizes["users"]), ("u{}",))
    teams = Kind("team", users.stop, int(sizes["teams"]),
                 (sizes["team_namespace"], "team{}", "member"))
    repos = []
    start = teams.stop
    for role in ROLES:
        repos.append(Kind(f"repo_{role}", start, int(sizes["repos"]),
                          (sizes["repo_namespace"], "repo{}", role)))
        start = repos[-1].stop
    mix = sizes["mix"]
    n_repo_roles = len(ROLES) * int(sizes["repos"])

    def membership(rng, k):
        return pick(teams, rng, k), pick(users, rng, k)

    def nesting(rng, k):
        return pick(teams, rng, k), pick(teams, rng, k)

    def grant(rng, k):
        src = repos[0].start + rng.integers(n_repo_roles, size=k)
        to_team = rng.random(k) < mix["grant_to_team"]
        dst = np.where(to_team, pick(teams, rng, k), pick(users, rng, k))
        return src, dst

    stages = [
        membership(rng, int(n * mix["membership"])),
        nesting(rng, int(n * mix["nesting"])),
    ]
    src, dst = fill(stages, grant, n, repos[-1].stop, rng)

    return Graph(
        kinds=[users, teams, *repos],
        src=src,
        dst=dst,
        objects="repo_pull",
        subjects="user",
        grants=tuple(r.name for r in repos),
    )
