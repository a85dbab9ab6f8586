select UserID, count(*) as c
from hits
group by UserID
order by c desc, UserID
limit 10
